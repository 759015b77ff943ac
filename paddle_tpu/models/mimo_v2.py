"""MiMo-V2 language-model family (``model_type: mimo_v2``) — served
through the continuous-batching engine.

What the family mixes, layer by layer (config.json keys in brackets):

* **attention of two kinds** [``hybrid_layer_pattern``]: *full* causal
  layers and *window* layers that see the last ``sliding_window`` keys
  and carry a learned per-head sink logit
  [``add_swa_attention_sink_bias``].  The two kinds have their own
  number of key-value heads [``num_key_value_heads``,
  ``swa_num_key_value_heads``] and their own rotary base
  [``rope_theta``, ``swa_rope_theta``]; keys are ``head_dim`` wide,
  values ``v_head_dim``, the rotation covers the first
  ``partial_rotary_factor * head_dim`` dimensions of a head (pairs
  ``(i, i + rot/2)``), and values are scaled by
  ``attention_value_scale`` before the weighted sum;
* **feed-forward of two kinds** [``moe_layer_freq``]: dense SwiGLU, or
  ``n_routed_experts`` SwiGLU experts behind a sigmoid router with a
  selection bias [``scoring_func``, ``topk_method: noaux_tc``] of which
  each row takes ``num_experts_per_tok``, weights renormalised
  [``norm_topk_prob``], no shared expert.

An instance holds ONE CHIP'S SHARE of such a model: every expert layer
keeps ``held_experts = (first, count)`` of the ``n_routed_experts`` (the
router stays whole), which is what a chip of an expert-parallel
deployment holds.  With the default ``(0, n_routed_experts)`` it is the
whole model.

The class carries parameters and the description the serving stack
asks for — ``config.description()``, ``described_params()`` and
``build_ragged_decode_step()`` (``models.generation``) — and no eager
forward: the engine is its path.  ``benchmark/reference/mimo_v2.py``
is the plain full-sequence forward it is held to.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from .. import nn
from ..framework.param_attr import ParamAttr
from ..nn.initializer import Constant, Normal
from .generation import (AttentionKind, FeedForwardKind,
                         LayerDescription, ModelDescription, _rope_key)

__all__ = ["MiMoV2Config", "MiMoV2ForCausalLM"]


@dataclass
class MiMoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_heads: int = 64
    num_kv_heads: int = 4                 # full layers
    swa_num_kv_heads: int = 8             # window layers
    head_dim: int = 192                   # queries and keys
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7               # full layers
    swa_rope_theta: float = 1e4           # window layers
    sliding_window: int = 128
    # 0: full attention, 1: window attention; its length is the depth
    hybrid_layer_pattern: List[int] = field(
        default_factory=lambda: [0, 1, 1, 1, 1, 0])
    # 0: dense feed-forward, 1: routed experts
    moe_layer_freq: List[int] = field(
        default_factory=lambda: [0, 1, 1, 1, 1, 1])
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256           # the router's width
    num_experts_per_tok: int = 8
    held_experts: Optional[Tuple[int, int]] = None   # (first, count)
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    rms_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        if len(self.hybrid_layer_pattern) != len(self.moe_layer_freq):
            raise ValueError(
                "hybrid_layer_pattern and moe_layer_freq describe the "
                f"same layers: {len(self.hybrid_layer_pattern)} and "
                f"{len(self.moe_layer_freq)} entries")
        if self.held_experts is None:
            self.held_experts = (0, int(self.n_routed_experts))
        first, count = (int(v) for v in self.held_experts)
        if first < 0 or count < 1 \
                or first + count > int(self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} lies outside the "
                f"{self.n_routed_experts} routed experts")
        self.held_experts = (first, count)
        for n in (self.num_kv_heads, self.swa_num_kv_heads):
            if int(self.num_heads) % int(n):
                raise ValueError(f"{self.num_heads} query heads do not "
                                 f"divide over {n} key-value heads")
        if self.tie_word_embeddings:
            raise ValueError("the family's head is untied")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def rotary_dim(self) -> int:
        """Rotated dimensions of a head, rounded down to even."""
        return int(self.partial_rotary_factor * self.head_dim) // 2 * 2

    def layer_descriptions(self) -> Tuple[LayerDescription, ...]:
        """What ``models.generation`` builds the ragged step and the
        serving engine its page pools from, layer by layer."""
        out = []
        for window, experts in zip(self.hybrid_layer_pattern,
                                   self.moe_layer_freq):
            if window:
                att = AttentionKind(
                    window=int(self.sliding_window),
                    kv_heads=int(self.swa_num_kv_heads),
                    key_dim=int(self.head_dim),
                    value_dim=int(self.v_head_dim),
                    rotary_dim=self.rotary_dim,
                    rope_theta=float(self.swa_rope_theta),
                    sink=bool(self.add_swa_attention_sink_bias),
                    value_scale=float(self.attention_value_scale))
            else:
                att = AttentionKind(
                    window=None, kv_heads=int(self.num_kv_heads),
                    key_dim=int(self.head_dim),
                    value_dim=int(self.v_head_dim),
                    rotary_dim=self.rotary_dim,
                    rope_theta=float(self.rope_theta),
                    sink=bool(self.add_full_attention_sink_bias),
                    value_scale=float(self.attention_value_scale))
            if experts:
                ff = FeedForwardKind(
                    width=int(self.moe_intermediate_size),
                    router_width=int(self.n_routed_experts),
                    top_k=int(self.num_experts_per_tok),
                    held=self.held_experts)
            else:
                ff = FeedForwardKind(width=int(self.intermediate_size))
            out.append(LayerDescription(att, ff))
        return tuple(out)

    def description(self) -> ModelDescription:
        # float32 served as float32: at jax's default a float32 product
        # is ONE bf16 pass on the MXU, which this model's logits check
        # could not tell from serving in bfloat16 (the program read
        # 1.3e-2..4.0e-2 of the largest logit over nine seeds, flipped
        # expert selections included, where the reference in bfloat16
        # reads 2.7e-2..3.9e-2; at "high", three passes, with the
        # attention kernel told "highest", it reads 6e-5: PERF.md
        # section 6, PR 27)
        return ModelDescription(
            self.layer_descriptions(), heads=int(self.num_heads),
            norm_eps=float(self.rms_eps), precision="high")


class _Block(nn.Layer):
    """One decoder layer's parameters, weights ``[in, out]``."""

    def __init__(self, c: MiMoV2Config, d: LayerDescription):
        super().__init__()
        h, nh = int(c.hidden_size), int(c.num_heads)
        att, ff = d.attention, d.feed_forward
        w = ParamAttr(initializer=Normal(0.0, c.initializer_range))
        one = ParamAttr(initializer=Constant(1.0))
        zero = ParamAttr(initializer=Constant(0.0))
        self.ln1 = self.create_parameter([h], attr=one)
        # fused projection: queries, then keys, then values
        self.wqkv = self.create_parameter(
            [h, nh * att.key_dim + att.kv_heads
             * (att.key_dim + att.value_dim)], attr=w)
        self.wo = self.create_parameter([nh * att.value_dim, h], attr=w)
        self.sink = self.create_parameter([nh], attr=zero) \
            if att.sink else None
        self.ln2 = self.create_parameter([h], attr=one)
        if ff.held is None:
            self.wg = self.create_parameter([h, ff.width], attr=w)
            self.wu = self.create_parameter([h, ff.width], attr=w)
            self.wd = self.create_parameter([ff.width, h], attr=w)
        else:
            count = ff.held[1]
            # scores that spread whatever the width: unit-variance
            # logits for a normalised row
            self.router_w = self.create_parameter(
                [h, ff.router_width],
                attr=ParamAttr(initializer=Normal(0.0, h ** -0.5)))
            # steers the selection only (e_score_correction_bias)
            self.router_b = self.create_parameter([ff.router_width],
                                                  attr=zero)
            # an array an expert, not one stacked array: the ragged step
            # runs a held expert's branch only where it has a row, and
            # a branch must be handed its own matrices and no others
            # (ops/routed_experts.py)
            several = lambda shape: nn.ParameterList(
                [self.create_parameter(shape, attr=w)
                 for _ in range(count)])
            self.wg = several([h, ff.width])
            self.wu = several([h, ff.width])
            self.wd = several([ff.width, h])


def _rope_tables(rot: int, max_pos: int, theta: float):
    """``cos, sin [max_pos, rot]`` for the half rotation (pairs
    ``(i, i + rot/2)``): the ``rot/2`` angles repeated side by side."""
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype="float64") / rot))
    ang = np.outer(np.arange(max_pos, dtype="float64"), inv)
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype("float32"), np.sin(ang).astype("float32")


class MiMoV2ForCausalLM(nn.Layer):
    def __init__(self, config: MiMoV2Config):
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
            "MiMoV2ForCausalLM has no eager forward: serve it through "
            "paddle_tpu.serving.ServingEngine (build_ragged_decode_step)")

    def described_params(self):
        """The tree the ragged step reads: ``embed``,
        ``norm_w``, ``lm_w``, ``rope`` (``theta -> (cos, sin)``, one
        pair of tables per rotary base, named by ``_rope_key``) and
        ``layers``."""
        import jax.numpy as jnp
        c = self.config
        rope = {}
        for d in self.descriptions:
            name = _rope_key(d.attention.rope_theta)
            if name not in rope:
                cos, sin = _rope_tables(d.attention.rotary_dim,
                                        int(c.max_position_embeddings),
                                        d.attention.rope_theta)
                rope[name] = (jnp.asarray(cos), jnp.asarray(sin))
        layers = []
        for blk in self.blocks:
            lp = {"ln1_w": blk.ln1._data, "wqkv": blk.wqkv._data,
                  "wo": blk.wo._data, "ln2_w": blk.ln2._data,
                  "sink": None if blk.sink is None else blk.sink._data}
            if hasattr(blk, "router_w"):
                lp["router_w"] = blk.router_w._data
                lp["router_b"] = blk.router_b._data
                for name in ("wg", "wu", "wd"):
                    lp[name] = tuple(p._data for p in getattr(blk, name))
            else:
                lp.update(wg=blk.wg._data, wu=blk.wu._data,
                          wd=blk.wd._data)
            layers.append(lp)
        return {"embed": self.embed._data, "norm_w": self.norm._data,
                "lm_w": self.lm_head._data, "rope": rope,
                "layers": layers}

    def build_ragged_decode_step(self):
        """Batched serving-engine step over per-layer page pools.  See
        models.generation.build_ragged_decode_step."""
        from .generation import build_ragged_decode_step
        return build_ragged_decode_step(self)

    def build_fused_window_step(self, max_window: int):
        from .generation import build_fused_window_step
        return build_fused_window_step(self, max_window)
