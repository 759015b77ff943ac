"""Attention functional ops.

ref: python/paddle/nn/functional/flash_attention.py — the reference binds
the flashattn CUDA library.  TPU-native path: `jax.nn.dot_product_attention`
(XLA emits a fused flash-style kernel on TPU) with a Pallas kernel hook for
the hot path (see paddle_tpu/ops/pallas/).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.dispatch import call_op
from ...core.tensor import Tensor
from ...tensor._helpers import ensure_tensor
from ...random_state import next_key
from ...flags import get_flag


_seg_par_mod = None


def _segment_parallel():
    # imported lazily (fleet pulls nn.Layer at import time — a module-
    # level import here would cycle), cached after the first call
    global _seg_par_mod
    if _seg_par_mod is None:
        from ...distributed.fleet.meta_parallel import (
            segment_parallel as _sp)
        _seg_par_mod = _sp
    return _seg_par_mod


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0,
                                 is_causal: bool = False,
                                 training: bool = True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (the reference's flash
    attention layout)."""
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    hq, hkv = query.shape[2], key.shape[2]

    def _expand_kv():
        # GQA kv-head broadcast for paths that need equal head counts
        nonlocal key, value, hkv
        if hkv != hq:
            rep = hq // hkv
            key = call_op(lambda a: jnp.repeat(a, rep, axis=2), (key,),
                          op_name="gqa_repeat")
            value = call_op(lambda a: jnp.repeat(a, rep, axis=2), (value,),
                            op_name="gqa_repeat")
            hkv = hq

    # sequence/context parallelism: when the fleet topology carries a
    # sep (Ulysses) or cp (ring) axis, attention itself is the op that
    # must run sequence-sharded — route it before the local hot paths
    sp = _segment_parallel()
    if sp.active_seq_parallel_axis() is not None:
        _expand_kv()
        out = sp.segment_parallel_attention(query, key, value, attn_mask,
                                            dropout_p, is_causal, training)
        if out is not None:
            return out
    has_mask = attn_mask is not None
    # hot path: Pallas flash kernel (no mask, no dropout, aligned
    # shapes; GQA kv heads broadcast in-kernel, decode sq<sk supported)
    if not has_mask and (dropout_p == 0.0 or not training):
        from ...ops.pallas import flash_attention as _pfa
        reason = True
        if _pfa.available():
            reason = _pfa.reject_reason(
                query.shape[1], key.shape[1], query.shape[-1], is_causal,
                hq, hkv, query.shape[0])
            if reason is not None:
                # the user ASKED for the flash path (flag on, backend
                # eligible) and a shape detail silently denied it —
                # tell them once per cause, keep counts queryable
                _pfa.note_fallback(reason)
        if reason is None:
            # a selected kernel that fails raises: no XLA retry
            return _pfa.pallas_flash_attention(query, key, value,
                                               causal=is_causal)
    _expand_kv()
    args = [query, key, value]
    if has_mask:
        args.append(ensure_tensor(attn_mask))
    drop_key = next_key() if (dropout_p > 0.0 and training) else None

    def f(q, k, v, *rest):
        mask = rest[0] if has_mask else None
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
        # [B, S, H, D] → [B, H, S, D]
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt).astype(jnp.float32) * scale
        if is_causal:
            s, t = logits.shape[-2], logits.shape[-1]
            causal = jnp.tril(jnp.ones((s, t), dtype=bool), t - s)
            logits = jnp.where(causal, logits, -1e30)
        if mask is not None:
            if mask.dtype == jnp.bool_:
                logits = jnp.where(mask, logits, -1e30)
            else:
                logits = logits + mask.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        if drop_key is not None:
            keep = 1.0 - dropout_p
            m = jax.random.bernoulli(drop_key, keep, probs.shape)
            probs = jnp.where(m, probs / keep, 0.0).astype(q.dtype)
        out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
        return jnp.swapaxes(out, 1, 2)
    return call_op(f, tuple(args), {}, op_name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout: float = 0.0,
                    causal: bool = False, return_softmax: bool = False,
                    fixed_seed_offset=None, rng_name: str = "",
                    training: bool = True, name=None):
    """ref: nn/functional/flash_attention.py flash_attention — returns
    (out, softmax_lse placeholder).  Uses the Pallas TPU kernel when
    enabled, else the XLA fused path."""
    # routing (incl. the Pallas hot path) lives in sdpa — one gate
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return (out, None)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen flash attention — reference packs ragged batches; here we run
    the dense kernel per max length with a padding mask built from the
    cumulative sequence lengths."""
    query, key, value = (ensure_tensor(query), ensure_tensor(key),
                         ensure_tensor(value))
    cu_q = ensure_tensor(cu_seqlens_q)

    def f(q, k, v, cu):
        # [total, H, D] packed → process as one long sequence with a block
        # mask disallowing cross-sequence attention
        total = q.shape[0]
        seq_id = jnp.cumsum(
            jnp.zeros((total,), jnp.int32).at[cu[1:-1]].add(1))
        mask = seq_id[:, None] == seq_id[None, :]
        if causal:
            mask = mask & (jnp.arange(total)[:, None] >= jnp.arange(total)[None, :])
        scale_ = scale
        logits = jnp.einsum("shd,thd->hst", q, k).astype(jnp.float32) * scale_
        logits = jnp.where(mask[None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("hst,thd->shd", probs, v)
    out = call_op(f, (query, key, value, cu_q), {},
                  op_name="flash_attn_unpadded")
    return (out, None)


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    from ... import dtype as dtypes
    x = ensure_tensor(x)
    jdt = dtypes.to_jax(dtype)
    ml = maxlen

    def f(v):
        m = ml if ml is not None else int(v.max())
        return (jnp.arange(m)[None, :] < v[..., None]).astype(jdt)
    if maxlen is None:
        # output width = max(x): data-dependent shape, must be host-read
        # before lowering (pass maxlen explicitly to stay trace-safe)
        m = int(x.numpy().max())  # noqa: PTL001
        return call_op(lambda v: (jnp.arange(m)[None, :] < v[..., None]).astype(jdt),
                       (x,), {}, op_name="sequence_mask")
    return call_op(f, (x,), {}, op_name="sequence_mask")


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """ref: nn/functional/sparse_attention.py — block-sparse attention
    where each query row attends only to the keys named by its CSR row
    (offset (B, H, S+1), columns (B, H, nnz)).

    TPU-native: the CSR pattern becomes a dense additive mask built
    inside the traced fn (searchsorted recovers each nonzero's row from
    the offsets, so the lowering is shape-static and jittable); the core
    is the standard masked softmax-matmul, which XLA tiles onto the MXU.
    The reference's CUDA kernel wins memory, not semantics — for long
    sequences use flash/ring attention instead.
    """
    from ...core.dispatch import call_op
    from ...tensor._helpers import ensure_tensor

    def fn(q, k, v, off, cols, *extra):
        B, H, S, D = q.shape
        nnz = cols.shape[-1]
        off = off.astype(jnp.int32)
        cols = cols.astype(jnp.int32)

        def one(off_bh, cols_bh):
            rows = jnp.searchsorted(off_bh, jnp.arange(nnz),
                                    side="right") - 1
            m = jnp.zeros((S, S), jnp.bool_)
            return m.at[rows, cols_bh].set(True)

        mask = jax.vmap(jax.vmap(one))(off, cols)        # (B, H, S, S)
        scores = jnp.einsum("bhsd,bhtd->bhst", q, k) / jnp.sqrt(
            jnp.asarray(D, q.dtype))
        neg = jnp.asarray(jnp.finfo(q.dtype).min, q.dtype)
        scores = jnp.where(mask, scores, neg)
        # both masks use the reference's 0-means-masked convention
        i = 0
        if key_padding_mask is not None:
            kp = extra[i]; i += 1
            scores = jnp.where(kp[:, None, None, :].astype(bool), scores,
                               neg)
        if attn_mask is not None:
            am = extra[i]; i += 1
            scores = jnp.where(am.astype(bool), scores, neg)
        p = jax.nn.softmax(scores, axis=-1)
        # fully-masked rows (empty CSR row) must output zeros, not nan
        p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
        return jnp.einsum("bhst,bhtd->bhsd", p, v)

    args = [ensure_tensor(query), ensure_tensor(key),
            ensure_tensor(value), ensure_tensor(sparse_csr_offset),
            ensure_tensor(sparse_csr_columns)]
    if key_padding_mask is not None:
        args.append(ensure_tensor(key_padding_mask))
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))
    return call_op(fn, tuple(args), op_name="sparse_attention")
