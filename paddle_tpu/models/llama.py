"""LLaMA model family — BASELINE config 4 flagship (LLaMA-2 7B/13B
hybrid tp x pp x dp).

Reference: PaddleNLP transformers/llama/modeling.py (LlamaModel with
RMSNorm, rotary embeddings, SwiGLU MLP, GQA) trained through
fleet.meta_parallel (mp_layers + PipelineLayer 1F1B + sequence-parallel
utils + recompute_hybrid) — survey §2.4 config 4.

TPU-native design notes:
- built from the fleet tensor-parallel layers exactly like the GPT/BERT
  flagships, so tp = GSPMD weight specs; pipeline via
  llama_pipeline_step (the same compiled ppermute-ring schedule with
  dropout-free blocks);
- RMSNorm/rotary lower through incubate fused functional (one fused XLA
  expression; the reference carries dedicated CUDA kernels);
- grouped-query attention (n_kv_heads < n_heads) repeats KV heads
  inside the traced graph — XLA fuses the broadcast into the attention
  matmuls.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..framework.param_attr import ParamAttr
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding)
from ..distributed.shard_utils import sharding_constraint
from ..distributed.fleet.recompute import recompute
from .generation import (AttentionKind, FeedForwardKind, LayerDescription,
                         ModelDescription, _rope_key)
import paddle_tpu as paddle

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "llama_config", "LLAMA_PRESETS"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None       # None → MHA
    intermediate_size: Optional[int] = None  # None → SwiGLU 8/3 rule
    max_position_embeddings: int = 4096
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    attention_bias: bool = False     # qkv biases (Qwen2-style)
    initializer_range: float = 0.02
    use_recompute: bool = False
    sequence_parallel: bool = False
    hidden_act: str = "silu"          # "silu" | "gelu_tanh" (Gemma)
    embed_scale: float = 1.0          # Gemma multiplies by sqrt(hidden)
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.hidden_act not in ("silu", "gelu_tanh"):
            raise ValueError(
                f"hidden_act={self.hidden_act!r} is not supported "
                "('silu' or 'gelu_tanh'); HF 'gelu_pytorch_tanh' maps "
                "to 'gelu_tanh'")
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            # llama rule: 2/3 * 4h rounded up to a multiple of 256
            inter = int(8 * self.hidden_size / 3)
            self.intermediate_size = 256 * ((inter + 255) // 256)

    def layer_descriptions(self):
        """A layer as ``models.generation`` serves it: full attention
        with ``num_kv_heads`` key-value heads, the whole head rotated as
        pairs ``(2i, 2i + 1)``, then a gated feed-forward."""
        hd = int(self.hidden_size) // int(self.num_heads)
        layer = LayerDescription(
            AttentionKind(window=None, kv_heads=int(self.num_kv_heads),
                          key_dim=hd, value_dim=hd, rotary_dim=hd,
                          rope_theta=float(self.rope_theta),
                          rope_interleaved=True),
            FeedForwardKind(width=int(self.intermediate_size),
                            act=self.hidden_act))
        return (layer,) * int(self.num_layers)

    def description(self) -> ModelDescription:
        return ModelDescription(
            self.layer_descriptions(), heads=int(self.num_heads),
            norm_eps=float(self.rms_eps),
            embed_scale=float(self.embed_scale),
            tied_head=bool(self.tie_word_embeddings))


LLAMA_PRESETS = {
    "llama2-7b": dict(num_layers=32, hidden_size=4096, num_heads=32,
                      intermediate_size=11008),
    "llama2-13b": dict(num_layers=40, hidden_size=5120, num_heads=40,
                       intermediate_size=13824),
    "llama2-70b": dict(num_layers=80, hidden_size=8192, num_heads=64,
                       num_kv_heads=8, intermediate_size=28672),
    "tiny": dict(num_layers=2, hidden_size=64, num_heads=4,
                 num_kv_heads=2, vocab_size=256,
                 max_position_embeddings=128),
}


def llama_config(name: str, **overrides) -> LlamaConfig:
    cfg = dict(LLAMA_PRESETS[name])
    cfg.update(overrides)
    return LlamaConfig(**cfg)


class LlamaRMSNorm(nn.Layer):
    """ref: modeling.LlamaRMSNorm → incubate fused_rms_norm."""

    def __init__(self, hidden_size: int, epsilon: float = 1e-5):
        super().__init__()
        from ..nn.initializer import Constant
        self.weight = self.create_parameter(
            shape=[hidden_size], attr=ParamAttr(initializer=Constant(1.0)))
        self.epsilon = epsilon

    def forward(self, x):
        from ..incubate.nn.functional import fused_rms_norm
        out, _ = fused_rms_norm(x, self.weight, epsilon=self.epsilon)
        return out


def _rope_cache(head_dim: int, max_pos: int, theta: float):
    """Full-width [S, head_dim] cos/sin (each pair's angle duplicated),
    the layout incubate fused_rotary_position_embedding consumes."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype="float32")
                           / head_dim))
    t = np.arange(max_pos, dtype="float32")
    freqs = np.outer(t, inv)                       # [S, hd/2]
    full = np.repeat(freqs, 2, axis=-1)            # [S, hd]
    return np.cos(full), np.sin(full)


class LlamaAttention(nn.Layer):
    """Rotary GQA attention over column/row-parallel projections."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.num_kv = c.num_kv_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.hidden_size = c.hidden_size
        init = ParamAttr(initializer=Normal(std=c.initializer_range))
        qkv_bias = bool(getattr(c, "attention_bias", False))
        self.q_proj = ColumnParallelLinear(
            c.hidden_size, c.num_heads * self.head_dim, weight_attr=init,
            has_bias=qkv_bias, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            c.hidden_size, self.num_kv * self.head_dim, weight_attr=init,
            has_bias=qkv_bias, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            c.hidden_size, self.num_kv * self.head_dim, weight_attr=init,
            has_bias=qkv_bias, gather_output=False)
        self.o_proj = RowParallelLinear(
            c.num_heads * self.head_dim, c.hidden_size, weight_attr=init,
            has_bias=False, input_is_parallel=True)
        cos, sin = _rope_cache(self.head_dim, c.max_position_embeddings,
                               c.rope_theta)
        self._cos, self._sin = jnp.asarray(cos), jnp.asarray(sin)

    def forward(self, x, past=None, use_cache: bool = False):
        """``past``: optional (k, v) cache of shape [B, S_past, Hkv, D]
        (kv heads UN-broadcast — the decode-shape flash kernel and the
        XLA bottom-right causal mask both consume sq < sk directly).
        With ``use_cache`` returns (out, (k_full, v_full))."""
        from ..incubate.nn.functional import fused_rotary_position_embedding
        from ..ops.paged_attention import PagedLayerView
        B, S, H = x.shape
        if isinstance(past, PagedLayerView):
            # serving decode: one token per sequence against the page
            # pool — per-row rope positions (lengths differ), append to
            # the pages, attend through paged_attention
            if S != 1:
                raise ValueError("paged decode feeds one token per step")
            lens = past.lengths_np()
            if int(lens.max()) + 1 > self._cos.shape[0]:
                raise ValueError(
                    f"sequence position {int(lens.max()) + 1} exceeds "
                    f"max_position_embeddings {self._cos.shape[0]}")
            q = self.q_proj(x).reshape([B, S, self.num_heads,
                                        self.head_dim])
            k = self.k_proj(x).reshape([B, S, self.num_kv, self.head_dim])
            v = self.v_proj(x).reshape([B, S, self.num_kv, self.head_dim])
            cos = Tensor(self._cos[lens][:, None])     # [B, 1, D]
            sin = Tensor(self._sin[lens][:, None])
            q, k, _ = fused_rotary_position_embedding(
                q, k, sin=sin, cos=cos, use_neox_rotary_style=False)
            out = past.append_and_attend(q, k, v)      # [B, nh, hd]
            out = out.reshape([B, 1, self.num_heads * self.head_dim])
            out = self.o_proj(out)
            return (out, past) if use_cache else out
        pos0 = past[0].shape[1] if past is not None else 0
        if pos0 + S > self._cos.shape[0]:
            raise ValueError(
                f"sequence position {pos0 + S} exceeds "
                f"max_position_embeddings {self._cos.shape[0]} — the "
                "rope table has no entries past that point")
        q = self.q_proj(x).reshape([B, S, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([B, S, self.num_kv, self.head_dim])
        v = self.v_proj(x).reshape([B, S, self.num_kv, self.head_dim])
        cos = Tensor(self._cos[pos0:pos0 + S])
        sin = Tensor(self._sin[pos0:pos0 + S])
        q, k, _ = fused_rotary_position_embedding(
            q, k, sin=sin, cos=cos, use_neox_rotary_style=False)
        if past is not None:
            k = paddle.concat([past[0], k], axis=1)
            v = paddle.concat([past[1], v], axis=1)
        new_past = (k, v) if use_cache else None
        # GQA kv heads stay un-broadcast: sdpa repeats only for paths
        # that need it (the Pallas kernel broadcasts in its index maps)
        q = sharding_constraint(q, None, None, "mp", None)
        k = sharding_constraint(k, None, None, "mp", None)
        v = sharding_constraint(v, None, None, "mp", None)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = out.reshape([B, S, self.num_heads * self.head_dim])
        out = sharding_constraint(out, None, None, "mp")
        out = self.o_proj(out)
        return (out, new_past) if use_cache else out


class LlamaMLP(nn.Layer):
    """SwiGLU (ref: modeling.LlamaMLP gate/up/down)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        init = ParamAttr(initializer=Normal(std=c.initializer_range))
        self.gate_proj = ColumnParallelLinear(
            c.hidden_size, c.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(
            c.hidden_size, c.intermediate_size, weight_attr=init,
            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(
            c.intermediate_size, c.hidden_size, weight_attr=init,
            has_bias=False, input_is_parallel=True)

        self._act = config.hidden_act

    def forward(self, x):
        g = self.gate_proj(x)
        a = (F.gelu(g, approximate=True) if self._act == "gelu_tanh"
             else F.silu(g))
        return self.down_proj(a * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, past=None, use_cache: bool = False):
        if use_cache:
            h, new_past = self.self_attn(self.input_layernorm(x),
                                         past=past, use_cache=True)
            x = x + h
            return x + self.mlp(self.post_attention_layernorm(x)), \
                new_past
        x = x + self.self_attn(self.input_layernorm(x), past=past)
        return x + self.mlp(self.post_attention_layernorm(x))


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        c = config
        self.embed_tokens = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(
                std=c.initializer_range)))
        self.layers = nn.LayerList([LlamaDecoderLayer(c)
                                    for _ in range(c.num_layers)])
        self.norm = LlamaRMSNorm(c.hidden_size, c.rms_eps)

    def forward(self, input_ids, past=None, use_cache: bool = False):
        c = self.config
        x = self.embed_tokens(input_ids)
        if c.embed_scale != 1.0:
            x = x * c.embed_scale
        from ..distributed.fleet.meta_parallel.segment_parallel import (
            active_seq_parallel_axis)
        seq_axis = active_seq_parallel_axis()
        if seq_axis is not None:
            x = sharding_constraint(x, ("dp", "sharding"), seq_axis[0],
                                    None)
        elif c.sequence_parallel:
            x = sharding_constraint(x, ("dp", "sharding"), "mp", None)
        else:
            x = sharding_constraint(x, ("dp", "sharding"), None, None)
        if use_cache:
            new_pasts = []
            for i, layer in enumerate(self.layers):
                x, p = layer(x, past=past[i] if past is not None else None,
                             use_cache=True)
                new_pasts.append(p)
            return self.norm(x), new_pasts
        for i, layer in enumerate(self.layers):
            if past is not None:
                # a provided cache must be consumed even when the caller
                # doesn't want a new one — dropping it would score the
                # tokens with no history
                x = layer(x, past=past[i])
            elif c.use_recompute and self.training:
                x = recompute(layer, x)
            else:
                x = layer(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer):
    """ref: modeling.LlamaForCausalLM — lm_head + criterion."""

    supports_paged_cache = True   # attention dispatches on PagedLayerView

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head_weight = self.create_parameter(
                shape=[config.vocab_size, config.hidden_size],
                attr=ParamAttr(initializer=Normal(
                    std=config.initializer_range)))
        self.loss_fn = LlamaPretrainingCriterion()

    def forward(self, input_ids, past=None, use_cache: bool = False,
                last_logits_only: bool = False):
        if use_cache:
            h, new_past = self.llama(input_ids, past=past, use_cache=True)
        else:
            h = self.llama(input_ids, past=past)
        if last_logits_only:
            # decode only samples the last position — skip the [S, V]
            # lm_head matmul for the rest of the prompt
            h = h[:, -1:]
        w = (self.llama.embed_tokens.weight
             if self.config.tie_word_embeddings else self.lm_head_weight)
        logits = paddle.matmul(h, w, transpose_y=True)
        logits = sharding_constraint(logits, ("dp", "sharding"), None,
                                     "mp")
        return (logits, new_past) if use_cache else logits

    def generate(self, input_ids, **kwargs):
        """ref: PaddleNLP GenerationMixin.generate — greedy / sampling
        decode with the KV cache (see models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, **kwargs)

    def build_decode_step(self):
        """Cache-aware single-token forward usable under trace (the
        compiled ``decode_loop``'s per-token body): returns
        ``(params, step_fn)`` with ``step_fn(params, tok [B], caches,
        pos) -> (logits [B, V], caches)`` pure over FIXED-shape
        ``[B, S_total, n_kv, hd]`` caches — rope rows gathered at
        ``pos``, GQA heads expanded inside the fused attention."""
        return _build_llama_decode_step(self)

    def described_params(self):
        """The tree the ragged step reads (``models.generation.
        build_ragged_decode_step``): ``build_decode_step()``'s arrays,
        the same buffers, with the rotation's tables under their base's
        name."""
        p, _ = self.build_decode_step()
        return {"embed": p["embed"], "layers": p["layers"],
                "rope": {_rope_key(self.config.rope_theta):
                         (p["cos"], p["sin"])},
                "norm_w": p["norm_w"], "lm_w": p["lm_w"]}

    def build_ragged_decode_step(self):
        """Batched serving-engine step over paged KV pools (per-
        sequence lengths + page tables — ragged carries).  See
        models.generation.build_ragged_decode_step."""
        from .generation import build_ragged_decode_step
        return build_ragged_decode_step(self)

    def build_fused_window_step(self, max_window: int):
        """Persistent-program serving window: up to ``max_window``
        ragged batch iterations in one compiled ``lax.while_loop``.
        See models.generation.build_fused_window_step."""
        from .generation import build_fused_window_step
        return build_fused_window_step(self, max_window)


def _build_llama_decode_step(model: "LlamaForCausalLM"):
    from ..ops.pallas import fused_decode as _fd

    c = model.config
    llama = model.llama
    nh = c.num_heads
    nkv = c.num_kv_heads
    hd = c.hidden_size // nh
    tied = bool(c.tie_word_embeddings)
    act = c.hidden_act
    eps = float(c.rms_eps)
    scale = float(c.embed_scale)

    layers = []
    for lyr in llama.layers:
        att = lyr.self_attn
        layers.append({
            "ln1_w": lyr.input_layernorm.weight._data,
            "wq": att.q_proj.weight._data,
            "wk": att.k_proj.weight._data,
            "wv": att.v_proj.weight._data,
            "bq": None if att.q_proj.bias is None
            else att.q_proj.bias._data,
            "bk": None if att.k_proj.bias is None
            else att.k_proj.bias._data,
            "bv": None if att.v_proj.bias is None
            else att.v_proj.bias._data,
            "wo": att.o_proj.weight._data,
            "ln2_w": lyr.post_attention_layernorm.weight._data,
            "wg": lyr.mlp.gate_proj.weight._data,
            "wu": lyr.mlp.up_proj.weight._data,
            "wd": lyr.mlp.down_proj.weight._data,
        })
    # the rope tables are identical across layers (same config)
    att0 = llama.layers[0].self_attn
    params = {
        "embed": llama.embed_tokens.weight._data,
        "cos": att0._cos, "sin": att0._sin,
        "layers": layers,
        "norm_w": llama.norm.weight._data,
        "lm_w": None if tied else model.lm_head_weight._data,
    }

    def step_fn(p, tok, caches, pos):
        x = jnp.take(p["embed"], tok, axis=0)
        if scale != 1.0:
            x = x * scale
        cos_row = jnp.take(p["cos"], pos, axis=0)     # [hd]
        sin_row = jnp.take(p["sin"], pos, axis=0)
        new_caches = []
        for i, lp in enumerate(p["layers"]):
            h = _fd.reference_rms_norm(x, lp["ln1_w"], eps)
            q, k, v = _fd.rope_qkv(h, lp["wq"], lp["wk"], lp["wv"],
                                   lp["bq"], lp["bk"], lp["bv"],
                                   cos_row, sin_row, n_heads=nh,
                                   n_kv=nkv, head_dim=hd, neox=False)
            ctx, kc, vc = _fd.attend_cache_append(
                q, k, v, caches[i][0], caches[i][1], pos)
            new_caches.append((kc, vc))
            x = x + jnp.matmul(ctx.reshape(-1, nh * hd), lp["wo"])
            x = x + _fd.norm_mlp(x, kind="rms_norm",
                                 norm_w=lp["ln2_w"], w_gate=lp["wg"],
                                 w1=lp["wu"], w2=lp["wd"], eps=eps,
                                 act=act)
        h = _fd.reference_rms_norm(x, p["norm_w"], eps)
        w = p["embed"] if tied else p["lm_w"]
        logits = jnp.matmul(h, jnp.swapaxes(w, -1, -2))
        return logits, tuple(new_caches)

    return params, step_fn


class LlamaPretrainingCriterion(nn.Layer):
    """Next-token CE, vocab-parallel safe (ref: same name)."""

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-100)

    def forward(self, logits, labels):
        B, S, V = logits.shape
        flat = labels.reshape([B * S])
        loss = self.ce(logits.reshape([B * S, V]), flat)
        mask = (flat != self.ce.ignore_index).astype(loss.dtype)
        return (loss * mask).sum() / mask.sum().clip(min=1.0)


def llama_pipeline_step(model: LlamaForCausalLM, optimizer, mesh,
                        n_micro: int, axis_name: str = "pp",
                        dp_axes=("dp", "sharding"),
                        remat_blocks: bool = True, n_chunks: int = 1,
                        scaler=None, autocast=None):
    """Pipeline schedule for LLaMA (config 4's pp leg): pre = token
    embedding, blocks = decoder layers (stacked over pp), post =
    final RMSNorm + lm_head + CE.  Stacking/VPP/sync mechanics come
    from the shared make_transformer_pipeline_step builder."""
    import jax as _jax
    from ..distributed.fleet.meta_parallel.pp_spmd import (
        make_transformer_pipeline_step)

    llama = model.llama
    cfg = model.config
    emb_w = llama.embed_tokens.weight
    norm_w = llama.norm.weight
    rep_tensors = [emb_w, norm_w] + (
        [] if cfg.tie_word_embeddings else [model.lm_head_weight])

    def pre_fn(rep_v, ids):
        h = jnp.take(rep_v[0], ids, axis=0)
        if cfg.embed_scale != 1.0:      # Gemma's sqrt(hidden) scaling
            h = h * jnp.asarray(cfg.embed_scale, h.dtype)
        return h

    def post_fn(rep_v, h, labels):
        nw = rep_v[1]
        hw = rep_v[0] if cfg.tie_word_embeddings else rep_v[2]
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        hn = h * _jax.lax.rsqrt(var + cfg.rms_eps) * nw
        logits = jnp.einsum("bsh,vh->bsv", hn, hw).astype(jnp.float32)
        lse = _jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
        mask = (labels != -100).astype(jnp.float32)
        return ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    return make_transformer_pipeline_step(
        llama.layers, rep_tensors, pre_fn, post_fn, optimizer, mesh,
        n_micro, axis_name=axis_name, dp_axes=dp_axes,
        remat_blocks=remat_blocks, n_chunks=n_chunks,
        stack_prefix="llama_pp_stack", scaler=scaler, autocast=autocast)
