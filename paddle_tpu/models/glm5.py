"""GLM-5 language-model family (``model_type: glm_moe_dsa``) — served
through the continuous-batching engine.

What the family mixes, layer by layer (config.json keys in brackets):

* **one token mixer, latent attention whose keys an index picks**:
  queries through a normed latent [``q_lora_rank``] as
  ``num_attention_heads`` heads of ``qk_nope_head_dim +
  qk_rope_head_dim``, the last part rotated (pairs ``(2i, 2i + 1)``
  [``rope_interleave``], base ``rope_theta``); keys and values through
  ONE normed latent of ``kv_lora_rank`` a token and one rotated key of
  ``qk_rope_head_dim`` that every head shares — the cache row of a
  token is those two side by side, and a head's keys (``w_uk``) and
  values (``w_uv``, ``v_head_dim`` wide) are read out of the latent; no
  bias [``attention_bias: false``].  An **index** [``index_n_heads``,
  ``index_head_dim``, ``index_topk``] scores every visible key for each
  query row — ``sum_h w_h relu(q_I,h . k_I)``, ``q_I`` from the query
  latent, ``k_I`` a layer norm of a projection of the layer's input,
  both partly rotated [``indexer_rope_interleave``] — and the row
  attends only the ``index_topk`` keys of the largest scores
  (``ops/latent_select.py``).  The index keys are cached beside the
  latent;
* **a feed-forward of two kinds**: the first ``first_k_dense_replace``
  layers a dense SwiGLU of ``intermediate_size``; every later layer
  ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``
  behind a sigmoid router with a selection bias [``topk_method:
  noaux_tc``; ``n_group = topk_group = 1``: a plain top-k of score +
  bias], of which each row takes ``num_experts_per_tok``, weights
  renormalised [``norm_topk_prob``] and scaled by
  ``routed_scaling_factor``, beside ``n_shared_experts`` shared SwiGLU
  of the same width that every row takes.

The multi-token-prediction layer [``num_nextn_predict_layers``] is not
part of the language model served here.

An instance holds ONE CHIP'S SHARE of such a model: every expert layer
keeps ``held_experts = (first, count)`` of the routed experts (the
router, the shared expert and the mixer stay whole: a latent cache has
no head to split).  With the default it is the whole model.

The class carries parameters and the description the serving stack
asks for — ``config.description()``, ``described_params()`` and
``build_ragged_decode_step()`` (``models.generation``) — and no eager
forward: the engine is its path.  ``benchmark/reference/glm5.py`` is the
plain forward it is held to, which expands every latent into a head's
keys and values where the served step works in the latent.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from .. import nn
from ..framework.param_attr import ParamAttr
from ..nn.initializer import Constant, Normal
from .generation import (FeedForwardKind, IndexKind, LatentAttentionKind,
                         LayerDescription, ModelDescription, _rope_key)

__all__ = ["Glm5Config", "Glm5ForCausalLM"]


@dataclass
class Glm5Config:
    vocab_size: int = 154880
    hidden_size: int = 6144
    num_hidden_layers: int = 78
    num_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rope_interleave: bool = True
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_rope_interleave: bool = True
    intermediate_size: int = 12288        # the leading dense layers
    first_k_dense_replace: int = 3
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256           # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    held_experts: Optional[Tuple[int, int]] = None   # (first, count)
    rms_eps: float = 1e-5
    max_position_embeddings: int = 8192
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.held_experts is None:
            self.held_experts = (0, int(self.n_routed_experts))
        first, count = (int(v) for v in self.held_experts)
        if first < 0 or count < 1 \
                or first + count > int(self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} lies outside the "
                f"{self.n_routed_experts} routed experts")
        self.held_experts = (first, count)
        if not 0 <= int(self.first_k_dense_replace) \
                <= int(self.num_hidden_layers):
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} lies "
                f"outside the {self.num_hidden_layers} layers")

    def layer_descriptions(self) -> Tuple[LayerDescription, ...]:
        """What ``models.generation`` builds the ragged step and the
        serving engine its caches from, layer by layer."""
        mixer = LatentAttentionKind(
            heads=int(self.num_heads), q_rank=int(self.q_lora_rank),
            kv_rank=int(self.kv_lora_rank),
            nope_dim=int(self.qk_nope_head_dim),
            rope_dim=int(self.qk_rope_head_dim),
            value_dim=int(self.v_head_dim),
            rope_theta=float(self.rope_theta),
            rope_interleaved=bool(self.rope_interleave),
            index=IndexKind(
                heads=int(self.index_n_heads), dim=int(self.index_head_dim),
                top_k=int(self.index_topk),
                rotary_dim=int(self.qk_rope_head_dim),
                rope_interleaved=bool(self.indexer_rope_interleave)))
        dense = FeedForwardKind(width=int(self.intermediate_size))
        experts = FeedForwardKind(
            width=int(self.moe_intermediate_size),
            router_width=int(self.n_routed_experts),
            top_k=int(self.num_experts_per_tok), held=self.held_experts,
            shared_width=int(self.n_shared_experts)
            * int(self.moe_intermediate_size),
            routed_scale=float(self.routed_scaling_factor))
        return tuple(
            LayerDescription(
                None, dense if i < int(self.first_k_dense_replace)
                else experts, latent_attention=mixer)
            for i in range(int(self.num_hidden_layers)))

    def description(self) -> ModelDescription:
        # float32 served as float32, as MiMo-V2's and Solar Open 2's
        # descriptions have it: the step's products at "high" (three
        # bf16 passes), the router's and the index's at "highest"
        return ModelDescription(
            self.layer_descriptions(), heads=int(self.num_heads),
            norm_eps=float(self.rms_eps), precision="high")


class _Block(nn.Layer):
    """One decoder layer's parameters, weights ``[in, out]``; a head's
    two read-outs of the latent are ``w_uk [heads, nope, kv_rank]`` and
    ``w_uv [heads, kv_rank, value]`` (a checkpoint's ``kv_b_proj``, cut
    in two by head)."""

    def __init__(self, c: Glm5Config, d: LayerDescription):
        super().__init__()
        h = int(c.hidden_size)
        w = ParamAttr(initializer=Normal(0.0, c.initializer_range))
        one = ParamAttr(initializer=Constant(1.0))
        zero = ParamAttr(initializer=Constant(0.0))
        make = self.create_parameter
        lat, ix = d.latent_attention, d.latent_attention.index
        self.ln1 = make([h], attr=one)
        self.wq_a = make([h, lat.q_rank], attr=w)
        self.q_norm = make([lat.q_rank], attr=one)
        self.wq_b = make([lat.q_rank,
                          lat.heads * (lat.nope_dim + lat.rope_dim)], attr=w)
        self.wkv_a = make([h, lat.kv_rank + lat.rope_dim], attr=w)
        self.kv_norm = make([lat.kv_rank], attr=one)
        self.w_uk = make([lat.heads, lat.nope_dim, lat.kv_rank], attr=w)
        self.w_uv = make([lat.heads, lat.kv_rank, lat.value_dim], attr=w)
        self.wo = make([lat.heads * lat.value_dim, h], attr=w)
        self.wi_q = make([lat.q_rank, ix.heads * ix.dim], attr=w)
        self.wi_k = make([h, ix.dim], attr=w)
        self.wi_k_norm_w = make([ix.dim], attr=one)
        self.wi_k_norm_b = make([ix.dim], attr=zero)
        self.wi_w = make([h, ix.heads], attr=w)
        ff = d.feed_forward
        self.ln2 = make([h], attr=one)
        if ff.held is None:
            self.wg = make([h, ff.width], attr=w)
            self.wu = make([h, ff.width], attr=w)
            self.wd = make([ff.width, h], attr=w)
            return
        # scores that spread whatever the width: unit-variance logits
        # for a normalised row
        self.router_w = make(
            [h, ff.router_width],
            attr=ParamAttr(initializer=Normal(0.0, h ** -0.5)))
        # steers the selection only
        self.router_b = make([ff.router_width], attr=zero)
        # an array an expert (ops/routed_experts.py)
        several = lambda shape: nn.ParameterList(
            [make(shape, attr=w) for _ in range(ff.held[1])])
        self.wg = several([h, ff.width])
        self.wu = several([h, ff.width])
        self.wd = several([ff.width, h])
        if ff.shared_width:
            self.shared_wg = make([h, ff.shared_width], attr=w)
            self.shared_wu = make([h, ff.shared_width], attr=w)
            self.shared_wd = make([ff.shared_width, h], attr=w)

    def described(self):
        """This layer's entry of ``described_params()["layers"]``: every
        parameter under the step body's name."""
        names = {"ln1": "ln1_w", "ln2": "ln2_w", "q_norm": "q_norm_w",
                 "kv_norm": "kv_norm_w"}
        out = {names.get(name, name): p._data for name, p in
               self.named_parameters(include_sublayers=False)}
        if hasattr(self, "router_w"):
            for name in ("wg", "wu", "wd"):
                out[name] = tuple(p._data for p in getattr(self, name))
        return out


def _rope_tables(rot: int, max_pos: int, theta: float, interleaved: bool):
    """``cos, sin [max_pos, rot]``: the ``rot / 2`` angles of a position
    laid out as the rotation pairs them — each twice in a row for pairs
    ``(2i, 2i + 1)``, else side by side for pairs ``(i, i + rot / 2)``."""
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype="float64") / rot))
    ang = np.outer(np.arange(max_pos, dtype="float64"), inv)
    ang = np.repeat(ang, 2, axis=-1) if interleaved \
        else np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype("float32"), np.sin(ang).astype("float32")


class Glm5ForCausalLM(nn.Layer):
    def __init__(self, config: Glm5Config):
        super().__init__()
        self.config = c = config
        self.descriptions = c.layer_descriptions()
        w = ParamAttr(initializer=Normal(0.0, c.initializer_range))
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], attr=w)
        self.blocks = nn.LayerList(
            [_Block(c, d) for d in self.descriptions])
        self.norm = self.create_parameter(
            [c.hidden_size], attr=ParamAttr(initializer=Constant(1.0)))
        self.lm_head = self.create_parameter(
            [c.vocab_size, c.hidden_size], attr=w)

    def forward(self, *args, **kwargs):
        raise NotImplementedError(
            "Glm5ForCausalLM has no eager forward: serve it through "
            "paddle_tpu.serving.ServingEngine (build_ragged_decode_step)")

    def seed_index(self, rs: np.random.RandomState) -> None:
        """Draw every layer's index off its initial state — the head
        weights' projection ten times wider (so that the heads of a row
        weigh unlike), the key norm's bias from U(-0.5, 0.5) and its
        weight from U(0.5, 1.5): a fresh model's unit weight and zero
        bias would let an index with no norm, and near-zero head weights
        one that picks anything, pass a comparison."""
        for blk in self.blocks:
            blk.wi_w.set_value(
                (np.asarray(blk.wi_w._data) * 10.0).astype(np.float32))
            dim = blk.wi_k_norm_b.shape[0]
            blk.wi_k_norm_b.set_value(
                rs.uniform(-0.5, 0.5, (dim,)).astype(np.float32))
            blk.wi_k_norm_w.set_value(
                rs.uniform(0.5, 1.5, (dim,)).astype(np.float32))

    def described_params(self):
        """The tree the ragged step reads: ``embed``, ``norm_w``,
        ``lm_w``, ``rope`` (the one rotary base's tables) and
        ``layers``."""
        import jax.numpy as jnp
        c = self.config
        cos, sin = _rope_tables(
            int(c.qk_rope_head_dim), int(c.max_position_embeddings),
            float(c.rope_theta), bool(c.rope_interleave))
        return {"embed": self.embed._data, "norm_w": self.norm._data,
                "lm_w": self.lm_head._data,
                "rope": {_rope_key(c.rope_theta):
                         (jnp.asarray(cos), jnp.asarray(sin))},
                "layers": [blk.described() for blk in self.blocks]}

    def build_ragged_decode_step(self):
        """Batched serving-engine step over per-layer caches.  See
        models.generation.build_ragged_decode_step."""
        from .generation import build_ragged_decode_step
        return build_ragged_decode_step(self)

    def build_fused_window_step(self, max_window: int):
        from .generation import build_fused_window_step
        return build_fused_window_step(self, max_window)
