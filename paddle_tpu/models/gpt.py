"""GPT model family — the flagship pretrain model (BASELINE config 3:
GPT-3 1.3B fleet dp+sharding; config 4 uses the same block structure).

TPU-native design notes (vs the reference's PaddleNLP-style GPT built on
fleet mp_layers + fused CUDA kernels):
- built from the fleet tensor-parallel layers (ColumnParallelLinear /
  RowParallelLinear / VocabParallelEmbedding) so tp comes from weight
  sharding specs and GSPMD, not hand collectives;
- attention math stays in plain jnp-backed ops so XLA fuses it; the
  Pallas flash-attention kernel slots in via
  paddle_tpu.nn.functional.flash_attention once seq length warrants it;
- activations optionally carry Megatron-SP sequence sharding between
  blocks (``sequence_parallel=True``);
- everything is bf16-friendly: params fp32 (master-weight pattern via
  amp O2), matmuls cast by amp auto_cast lists.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal, Constant
from ..framework.param_attr import ParamAttr
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    ParallelCrossEntropy)
from ..distributed.fleet.utils.sequence_parallel_utils import (
    AllGatherOp, ReduceScatterOp)
from ..distributed.shard_utils import sharding_constraint
from ..distributed.fleet.recompute import recompute
from .generation import (AttentionKind, FeedForwardKind, LayerDescription,
                         ModelDescription)
import paddle_tpu as paddle


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 16
    max_position_embeddings: int = 2048
    intermediate_size: Optional[int] = None  # default 4*hidden
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    use_recompute: bool = False
    sequence_parallel: bool = False
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size

    def layer_descriptions(self):
        """A block as ``models.generation`` serves it: full attention of
        ``num_heads`` heads and no rotation, then a GELU MLP."""
        hd = int(self.hidden_size) // int(self.num_heads)
        block = LayerDescription(
            AttentionKind(window=None, kv_heads=int(self.num_heads),
                          key_dim=hd, value_dim=hd),
            FeedForwardKind(width=int(self.intermediate_size),
                            act="gelu_tanh", gated=False))
        return (block,) * int(self.num_layers)

    def description(self) -> ModelDescription:
        return ModelDescription(
            self.layer_descriptions(), heads=int(self.num_heads),
            norm="layer_norm", norm_eps=1e-5, learned_positions=True,
            tied_head=bool(self.tie_word_embeddings))


PRESETS = {
    # name: (layers, hidden, heads, seq)
    "gpt3-125M": dict(num_layers=12, hidden_size=768, num_heads=12),
    "gpt3-350M": dict(num_layers=24, hidden_size=1024, num_heads=16),
    "gpt3-760M": dict(num_layers=24, hidden_size=1536, num_heads=16),
    "gpt3-1.3B": dict(num_layers=24, hidden_size=2048, num_heads=16),
    "gpt3-2.7B": dict(num_layers=32, hidden_size=2560, num_heads=32),
    "gpt3-6.7B": dict(num_layers=32, hidden_size=4096, num_heads=32),
    "gpt3-13B": dict(num_layers=40, hidden_size=5120, num_heads=40),
    "tiny": dict(num_layers=2, hidden_size=64, num_heads=4, vocab_size=512,
                 max_position_embeddings=128),
}


def gpt_config(name: str, **overrides) -> GPTConfig:
    cfg = dict(PRESETS[name])
    cfg.update(overrides)
    return GPTConfig(**cfg)


class MultiHeadAttention(nn.Layer):
    """Causal self-attention with fused qkv column-parallel projection."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.hidden_size = c.hidden_size
        self.attn_drop = c.attention_dropout_prob
        self.seq_par = c.sequence_parallel
        init = ParamAttr(initializer=Normal(std=c.initializer_range))
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, weight_attr=init,
            has_bias=True, gather_output=False)
        self.out_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, weight_attr=init, has_bias=True,
            input_is_parallel=True)

    def forward(self, x, training: bool = True, past=None,
                use_cache: bool = False):
        from ..ops.paged_attention import PagedLayerView
        B, S, H = x.shape
        qkv = self.qkv_proj(x)                     # [B, S, 3H] (mp-sharded)
        # flash layout [B, S, nh, hd]; heads are the mp-sharded dim
        qkv = qkv.reshape([B, S, 3, self.num_heads, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if isinstance(past, PagedLayerView):
            # serving decode against the page pool (no rope in GPT —
            # positions live in the embeddings)
            if S != 1:
                raise ValueError("paged decode feeds one token per step")
            out = past.append_and_attend(q, k, v)  # [B, nh, hd]
            out = out.reshape([B, 1, H])
            out = self.out_proj(out)
            return (out, past) if use_cache else out
        if past is not None:
            k = paddle.concat([past[0], k], axis=1)
            v = paddle.concat([past[1], v], axis=1)
        new_past = (k, v) if use_cache else None
        q = sharding_constraint(q, None, None, "mp", None)
        k = sharding_constraint(k, None, None, "mp", None)
        v = sharding_constraint(v, None, None, "mp", None)
        out = F.scaled_dot_product_attention(
            q, k, v, dropout_p=self.attn_drop if training else 0.0,
            is_causal=True, training=training)     # [B, S, nh, hd]
        out = out.reshape([B, S, H])
        out = sharding_constraint(out, None, None, "mp")
        out = self.out_proj(out)
        return (out, new_past) if use_cache else out


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        init = ParamAttr(initializer=Normal(std=c.initializer_range))
        proj_init = ParamAttr(initializer=Normal(
            std=c.initializer_range / math.sqrt(2.0 * c.num_layers)))
        self.fc1 = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                        weight_attr=init, has_bias=True,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(c.intermediate_size, c.hidden_size,
                                     weight_attr=proj_init, has_bias=True,
                                     input_is_parallel=True)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.ln1 = nn.LayerNorm(c.hidden_size, epsilon=1e-5)
        self.attn = MultiHeadAttention(c)
        self.ln2 = nn.LayerNorm(c.hidden_size, epsilon=1e-5)
        self.mlp = GPTMLP(c)
        self.drop_p = c.hidden_dropout_prob

    def forward(self, x, past=None, use_cache: bool = False):
        # the device trace's parts (generation.TRAIN_STEP_PARTS): each
        # holds its norm, its dropout and the residual's add
        with jax.named_scope("attention"):
            if use_cache:
                h, new_past = self.attn(self.ln1(x), training=self.training,
                                        past=past, use_cache=True)
            else:
                h = self.attn(self.ln1(x), training=self.training,
                              past=past)
            h = F.dropout(h, self.drop_p, training=self.training)
            x = x + h
        with jax.named_scope("mlp"):
            h = self.mlp(self.ln2(x))
            h = F.dropout(h, self.drop_p, training=self.training)
            x = x + h
        return (x, new_past) if use_cache else x


class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.word_embeddings = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(std=c.initializer_range)))
        self.position_embeddings = nn.Embedding(
            c.max_position_embeddings, c.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(std=c.initializer_range)))
        self.drop_p = c.hidden_dropout_prob

    @jax.named_scope("embed")
    def forward(self, input_ids, pos_offset: int = 0):
        S = input_ids.shape[-1]
        if pos_offset + S > self.position_embeddings.weight.shape[0]:
            raise ValueError(
                f"sequence position {pos_offset + S} exceeds "
                "max_position_embeddings "
                f"{self.position_embeddings.weight.shape[0]}")
        pos = paddle.arange(pos_offset, pos_offset + S, dtype="int64")
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        return F.dropout(x, self.drop_p, training=self.training)


class GPTModel(nn.Layer):
    """Transformer decoder stack."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList([GPTBlock(config)
                                    for _ in range(config.num_layers)])
        self.final_ln = nn.LayerNorm(config.hidden_size, epsilon=1e-5)

    def forward(self, input_ids, past=None, use_cache: bool = False):
        from ..ops.paged_attention import PagedLayerView
        c = self.config
        if past is not None and isinstance(past[0], PagedLayerView):
            lens = past[0].lengths_np()
            if len(set(lens.tolist())) != 1:
                raise ValueError(
                    "GPT's learned position embedding uses one batch-"
                    "wide offset; paged decode needs uniform lengths")
            pos0 = int(lens[0])
        else:
            pos0 = past[0][0].shape[1] if past is not None else 0
        x = self.embeddings(input_ids, pos_offset=pos0)
        # dp over batch; the sequence dim is sharded between blocks by
        # whichever long-context mechanism is live: sep/cp axis from the
        # fleet topology (Ulysses/ring — attention itself runs sharded),
        # else mp when Megatron-SP is on (attention gathers internally)
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
            for i, block in enumerate(self.layers):
                x, p = block(x, past=past[i] if past is not None
                             else None, use_cache=True)
                new_pasts.append(p)
            with jax.named_scope("lm_head"):
                return self.final_ln(x), new_pasts
        for i, block in enumerate(self.layers):
            if past is not None:
                x = block(x, past=past[i])
            elif c.use_recompute and self.training:
                x = recompute(block, x)
            else:
                x = block(x)
        with jax.named_scope("lm_head"):
            return self.final_ln(x)


class GPTForPretraining(nn.Layer):
    """LM head (tied to the word embedding) + loss."""

    supports_paged_cache = True   # attention dispatches on PagedLayerView

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head_weight = self.create_parameter(
                shape=[config.vocab_size, config.hidden_size],
                attr=ParamAttr(initializer=Normal(std=config.initializer_range)))
        self.loss_fn = GPTPretrainingCriterion()

    def forward(self, input_ids, past=None, use_cache: bool = False,
                last_logits_only: bool = False):
        if use_cache:
            h, new_past = self.gpt(input_ids, past=past, use_cache=True)
        else:
            h = self.gpt(input_ids, past=past)        # [B, S, H]
        with jax.named_scope("lm_head"):
            if last_logits_only:
                h = h[:, -1:]
            w = (self.gpt.embeddings.word_embeddings.weight
                 if self.config.tie_word_embeddings
                 else self.lm_head_weight)
            logits = paddle.matmul(h, w, transpose_y=True)  # [B, S, V]
            logits = sharding_constraint(logits, ("dp", "sharding"), None,
                                         "mp")
        return (logits, new_past) if use_cache else logits

    def generate(self, input_ids, **kwargs):
        """ref: PaddleNLP GenerationMixin.generate — KV-cache decode
        (see models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, **kwargs)

    def build_decode_step(self):
        """Cache-aware single-token forward usable under trace (the
        compiled ``decode_loop``'s per-token body): returns
        ``(params, step_fn)`` where ``step_fn(params, tok [B], caches,
        pos) -> (logits [B, V], caches)`` is a pure jnp function over
        FIXED-shape preallocated caches ``[B, S_total, nh, hd]`` —
        shapes never grow, so the whole loop lives in one
        ``lax.while_loop``.  Params ride as jit arguments (weight
        updates between calls never retrace)."""
        return _build_gpt_decode_step(self)

    def described_params(self):
        """The tree the ragged step reads (``models.generation.
        build_ragged_decode_step``): ``build_decode_step()``'s arrays,
        the same buffers, under the step's names.  ``blocks`` is what
        ``benchmark/runners/serve.py`` counts a GPT's layers by: an
        entry a layer that holds no array (ROADMAP D14)."""
        p, _ = self.build_decode_step()
        return {"embed": p["wte"], "positions": p["wpe"],
                "layers": p["blocks"], "blocks": (None,) * len(p["blocks"]),
                "norm_w": p["lnf_w"], "norm_b": p["lnf_b"],
                "lm_w": p["lm_w"]}

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


def _build_gpt_decode_step(model: "GPTForPretraining"):
    import jax.numpy as jnp

    from ..ops.pallas import fused_decode as _fd

    c = model.config
    gpt = model.gpt
    H = c.hidden_size
    nh = c.num_heads
    hd = H // nh
    tied = bool(c.tie_word_embeddings)

    blocks = []
    for blk in gpt.layers:
        qkv_w = blk.attn.qkv_proj.weight._data        # [H, 3H], packed
        qkv_b = blk.attn.qkv_proj.bias._data          # (3, nh, hd) cols
        blocks.append({
            "ln1_w": blk.ln1.weight._data, "ln1_b": blk.ln1.bias._data,
            "wq": qkv_w[:, :H], "wk": qkv_w[:, H:2 * H],
            "wv": qkv_w[:, 2 * H:],
            "bq": qkv_b[:H], "bk": qkv_b[H:2 * H], "bv": qkv_b[2 * H:],
            "wo": blk.attn.out_proj.weight._data,
            "bo": blk.attn.out_proj.bias._data,
            "ln2_w": blk.ln2.weight._data, "ln2_b": blk.ln2.bias._data,
            "w1": blk.mlp.fc1.weight._data, "b1": blk.mlp.fc1.bias._data,
            "w2": blk.mlp.fc2.weight._data, "b2": blk.mlp.fc2.bias._data,
        })
    params = {
        "wte": gpt.embeddings.word_embeddings.weight._data,
        "wpe": gpt.embeddings.position_embeddings.weight._data,
        "blocks": blocks,
        "lnf_w": gpt.final_ln.weight._data,
        "lnf_b": gpt.final_ln.bias._data,
        "lm_w": None if tied else model.lm_head_weight._data,
    }

    def step_fn(p, tok, caches, pos):
        x = jnp.take(p["wte"], tok, axis=0) \
            + jnp.take(p["wpe"], pos, axis=0)
        new_caches = []
        for i, bp in enumerate(p["blocks"]):
            h = _fd.reference_layer_norm(x, bp["ln1_w"], bp["ln1_b"],
                                         1e-5)
            q, k, v = _fd.rope_qkv(h, bp["wq"], bp["wk"], bp["wv"],
                                   bp["bq"], bp["bk"], bp["bv"],
                                   n_heads=nh, n_kv=nh, head_dim=hd)
            ctx, kc, vc = _fd.attend_cache_append(
                q, k, v, caches[i][0], caches[i][1], pos)
            new_caches.append((kc, vc))
            x = x + (jnp.matmul(ctx.reshape(-1, H), bp["wo"])
                     + bp["bo"])
            x = x + _fd.norm_mlp(x, kind="layer_norm",
                                 norm_w=bp["ln2_w"], norm_b=bp["ln2_b"],
                                 w1=bp["w1"], b1=bp["b1"],
                                 w2=bp["w2"], b2=bp["b2"],
                                 eps=1e-5, act="gelu_tanh")
        h = _fd.reference_layer_norm(x, p["lnf_w"], p["lnf_b"], 1e-5)
        w = p["wte"] if tied else p["lm_w"]
        logits = jnp.matmul(h, jnp.swapaxes(w, -1, -2))
        return logits, tuple(new_caches)

    return params, step_fn


class GPTPretrainingCriterion(nn.Layer):
    """Next-token cross entropy (vocab-parallel safe)."""

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-100)

    @jax.named_scope("lm_head")
    def forward(self, logits, labels):
        # logits [B, S, V]; labels [B, S].  Mean over VALID tokens only —
        # ignore_index positions must not dilute the loss (reference's
        # masked-sum / mask-count formulation).
        B, S, V = logits.shape
        flat = labels.reshape([B * S])
        loss = self.ce(logits.reshape([B * S, V]), flat)
        mask = (flat != self.ce.ignore_index).astype(loss.dtype)
        return (loss * mask).sum() / mask.sum().clip(min=1.0)
