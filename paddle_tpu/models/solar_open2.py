"""Solar Open 2 language-model family (``model_type: solar_open2``) —
served through the continuous-batching engine.

What the family mixes, layer by layer (config.json keys in brackets):

* **token mixers of two kinds** [``gqa_layers``]: a layer listed there
  is softmax attention with grouped key-value heads
  [``num_attention_heads``, ``num_key_value_heads``, ``head_dim``], no
  rotation and no position term at all [``use_rope: false``], and an
  elementwise sigmoid gate on the weighted sum before the output
  projection [``use_gqa_gate``]; every other layer is gated delta-rule
  linear attention (Kimi Delta Attention, arXiv:2510.26692)
  [``linear_attn_config``]: q, k and v through a causal depthwise
  convolution over the last ``short_conv_kernel_size`` positions and a
  silu, q and k normalised a head, a log decay a key channel from a
  low-rank pair of matrices, a write strength ``2 sigmoid(.)`` a head
  [``kda_allow_neg_eigval``], the recurrence of
  ``ops/gated_delta.py``, a norm over each head's output and a
  low-rank sigmoid gate [``kda_use_full_proj: false``];
* **one feed-forward**: ``n_routed_experts`` SwiGLU experts of
  ``moe_intermediate_size`` behind a sigmoid router with a selection
  bias, of which each row takes ``num_experts_per_tok``, weights
  renormalised [``norm_topk_prob``] and scaled by
  ``routed_scaling_factor``, beside ``n_shared_experts`` shared SwiGLU
  of the same width that every row takes; no dense layer
  [``first_k_dense_replace: 0``].

An instance holds ONE CHIP'S SHARE of such a model: every expert layer
keeps ``held_experts = (first, count)`` of the routed experts (the
router and the shared expert stay whole).  With the default it is the
whole model.

The class carries parameters and the description the serving stack
asks for — ``config.description()``, ``described_params()`` and
``build_ragged_decode_step()`` (``models.generation``) — and no eager
forward: the engine is its path.  ``benchmark/reference/solar_open2.py``
is the plain token-by-token forward it is held to.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import numpy as np

from .. import nn
from ..framework.param_attr import ParamAttr
from ..nn.initializer import Constant, Normal
from .generation import (AttentionKind, FeedForwardKind, LayerDescription,
                         LinearAttentionKind, ModelDescription)

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM"]


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    # the softmax-attention layers; every other layer is linear attention
    gqa_layers: List[int] = field(
        default_factory=lambda: list(range(0, 48, 4)))
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    use_gqa_gate: bool = True
    linear_num_heads: int = 64
    linear_head_dim: int = 128            # keys and values
    short_conv_kernel_size: int = 4
    # width of the decay's and the output gate's low-rank pairs
    linear_low_rank: int = 128
    kda_allow_neg_eigval: bool = True
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320           # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    held_experts: Optional[Tuple[int, int]] = None   # (first, count)
    rms_eps: float = 1e-5
    max_position_embeddings: int = 8192
    initializer_range: float = 0.02

    def __post_init__(self):
        self.gqa_layers = sorted(int(i) for i in self.gqa_layers)
        if self.gqa_layers and not (
                0 <= self.gqa_layers[0]
                and self.gqa_layers[-1] < int(self.num_hidden_layers)):
            raise ValueError(f"gqa_layers {self.gqa_layers} lie outside "
                             f"the {self.num_hidden_layers} layers")
        if self.held_experts is None:
            self.held_experts = (0, int(self.n_routed_experts))
        first, count = (int(v) for v in self.held_experts)
        if first < 0 or count < 1 \
                or first + count > int(self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} lies outside the "
                f"{self.n_routed_experts} routed experts")
        self.held_experts = (first, count)
        if int(self.num_heads) % int(self.num_kv_heads):
            raise ValueError(f"{self.num_heads} query heads do not divide "
                             f"over {self.num_kv_heads} key-value heads")
        if int(self.linear_num_heads) != int(self.num_heads) \
                or int(self.linear_head_dim) != int(self.head_dim):
            raise ValueError(
                "the served step counts one number of query heads a "
                "model: linear-attention heads "
                f"{self.linear_num_heads} x {self.linear_head_dim} differ "
                f"from attention's {self.num_heads} x {self.head_dim}")

    def layer_descriptions(self) -> Tuple[LayerDescription, ...]:
        """What ``models.generation`` builds the ragged step and the
        serving engine its caches from, layer by layer."""
        ff = FeedForwardKind(
            width=int(self.moe_intermediate_size),
            router_width=int(self.n_routed_experts),
            top_k=int(self.num_experts_per_tok), held=self.held_experts,
            shared_width=int(self.n_shared_experts)
            * int(self.moe_intermediate_size),
            routed_scale=float(self.routed_scaling_factor))
        gqa = AttentionKind(
            window=None, kv_heads=int(self.num_kv_heads),
            key_dim=int(self.head_dim), value_dim=int(self.head_dim),
            gate=bool(self.use_gqa_gate))
        linear = LinearAttentionKind(
            heads=int(self.linear_num_heads),
            key_dim=int(self.linear_head_dim),
            value_dim=int(self.linear_head_dim),
            conv_kernel=int(self.short_conv_kernel_size),
            beta_scale=2.0 if self.kda_allow_neg_eigval else 1.0)
        return tuple(
            LayerDescription(gqa, ff) if i in self.gqa_layers
            else LayerDescription(None, ff, linear)
            for i in range(int(self.num_hidden_layers)))

    def description(self) -> ModelDescription:
        # float32 served as float32, as MiMo-V2's description has it:
        # the step's products at "high" (three bf16 passes), the
        # router's, the recurrence's and the attention kernel's at
        # "highest".  ("highest" throughout was measured: it moved no
        # seed's logits error — what separates the program from the
        # reference here is not the products' rounding — and cost 30 %
        # of the cell's tokens a second; PERF.md section 6, PR 31)
        return ModelDescription(
            self.layer_descriptions(), heads=int(self.num_heads),
            norm_eps=float(self.rms_eps), precision="high")


class _Block(nn.Layer):
    """One decoder layer's parameters, weights ``[in, out]``."""

    def __init__(self, c: SolarOpen2Config, d: LayerDescription):
        super().__init__()
        h = int(c.hidden_size)
        w = ParamAttr(initializer=Normal(0.0, c.initializer_range))
        one = ParamAttr(initializer=Constant(1.0))
        zero = ParamAttr(initializer=Constant(0.0))
        make = self.create_parameter
        self.ln1 = make([h], attr=one)
        if d.attention is not None:
            att = d.attention
            nq = int(c.num_heads)
            self.wq = make([h, nq * att.key_dim], attr=w)
            self.wk = make([h, att.kv_heads * att.key_dim], attr=w)
            self.wv = make([h, att.kv_heads * att.value_dim], attr=w)
            self.wgate = make([h, nq * att.value_dim], attr=w) \
                if att.gate else None
            self.wo = make([nq * att.value_dim, h], attr=w)
        else:
            lin = d.linear_attention
            nk, nv = lin.heads * lin.key_dim, lin.heads * lin.value_dim
            r, k = int(c.linear_low_rank), lin.conv_kernel
            self.wq = make([h, nk], attr=w)
            self.wk = make([h, nk], attr=w)
            self.wv = make([h, nv], attr=w)
            # depthwise taps [kernel, channels], no bias; unit variance
            # through the K taps
            tap = ParamAttr(initializer=Normal(0.0, k ** -0.5))
            self.conv_q = make([k, nk], attr=tap)
            self.conv_k = make([k, nk], attr=tap)
            self.conv_v = make([k, nv], attr=tap)
            self.wf_down = make([h, r], attr=w)
            self.wf_up = make([r, nk], attr=w)
            self.dt_bias = make([nk], attr=zero)
            self.a_log = make([lin.heads], attr=zero)
            self.wbeta = make([h, lin.heads], attr=w)
            self.wgate_down = make([h, r], attr=w)
            self.wgate_up = make([r, nv], attr=w)
            self.out_norm = make([lin.value_dim], attr=one)
            self.wo = make([nv, h], attr=w)
        ff = d.feed_forward
        self.ln2 = make([h], attr=one)
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
        names = {"ln1": "ln1_w", "ln2": "ln2_w", "out_norm": "out_norm_w"}
        out = {names.get(name, name): p._data for name, p in
               self.named_parameters(include_sublayers=False)}
        for name in ("wg", "wu", "wd"):
            out[name] = tuple(p._data for p in getattr(self, name))
        return out


class SolarOpen2ForCausalLM(nn.Layer):
    def __init__(self, config: SolarOpen2Config):
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
            "SolarOpen2ForCausalLM has no eager forward: serve it through "
            "paddle_tpu.serving.ServingEngine (build_ragged_decode_step)")

    def seed_decays(self, rs: np.random.RandomState,
                    slowest: float = 0.999, fastest: float = 0.5) -> None:
        """Draw every linear layer's ``a_log`` from log U(1, 16) and its
        ``dt_bias`` so that, at a zero low-rank input, the decay a step
        ``exp(g)`` of its key channels spreads log-uniformly over
        ``(fastest, slowest)``: a fresh model's zeros would make every
        channel decay alike."""
        for blk in self.blocks:
            if not hasattr(blk, "a_log"):
                continue
            heads = blk.a_log.shape[0]
            a = rs.uniform(1.0, 16.0, (heads,))
            blk.a_log.set_value(np.log(a).astype(np.float32))
            # -log(decay) = a * softplus(dt_bias), log-uniform in decay
            lo, hi = -math.log(slowest), -math.log(fastest)
            rate = np.exp(rs.uniform(math.log(lo), math.log(hi),
                                     (heads, blk.dt_bias.shape[0] // heads)))
            soft = rate / a[:, None]
            blk.dt_bias.set_value(
                np.log(np.expm1(soft)).reshape(-1).astype(np.float32))

    def described_params(self):
        """The tree the ragged step reads: ``embed``, ``norm_w``,
        ``lm_w`` and ``layers``."""
        return {"embed": self.embed._data, "norm_w": self.norm._data,
                "lm_w": self.lm_head._data,
                "layers": [blk.described() for blk in self.blocks]}

    def build_ragged_decode_step(self):
        """Batched serving-engine step over per-layer caches.  See
        models.generation.build_ragged_decode_step."""
        from .generation import build_ragged_decode_step
        return build_ragged_decode_step(self)

    def build_fused_window_step(self, max_window: int):
        from .generation import build_fused_window_step
        return build_fused_window_step(self, max_window)
