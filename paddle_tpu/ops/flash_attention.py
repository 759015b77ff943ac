"""Pallas flash attention (TPU).

TPU-native replacement for the reference's flash-attn integration
(ref: paddle/phi/kernels/fusion/ + third_party/flashattn +
python/paddle/nn/functional/flash_attention.py).

Blockwise online-softmax attention: never materialises the S x S score
matrix.  Forward computes per-query-block running (max, sum, acc) over
key blocks (skipping fully-masked blocks under causal); backward is the
standard two-kernel flash recomputation (dq over key blocks, dk/dv over
query blocks) using the saved logsumexp.

Layout contract here is [B*H, S, D] (callers reshape); block sizes are
MXU-aligned (128).  ``interpret=True`` runs the same kernels on CPU for
tests (the fake-device strategy of SURVEY.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                causal: bool, block_q: int, block_k: int, seq_k: int,
                q_offset: int = 0):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [BQ, D]
    bq, d = q.shape

    # q_offset: global position of q row 0 — bottom-right causal
    # alignment for decode (sq < sk), 0 for self-attention
    hi = (jnp.int32(seq_k) if not causal
          else jnp.int32(q_offset) + (qi + 1) * jnp.int32(block_q))
    nblocks = pl.cdiv(hi, jnp.int32(block_k))

    def body(j, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            q_idx = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v_blk,
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nblocks, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse rides in a (bh, sq, 1) buffer: Mosaic requires the last two
    # block dims to be (8k, 128k) or equal to the array dims, which a
    # (1, block_q) block over (bh, sq) can never satisfy
    lse_ref[0] = m + jnp.log(l)


def _flash_fwd(q, k, v, scale: float, causal: bool, block_q: int,
               block_k: int, interpret: bool, q_offset: int = 0,
               n_rep: int = 1):
    """n_rep > 1 = GQA: q is [B*Hq, SQ, D], k/v are [B*Hkv, SK, D] with
    Hq = Hkv * n_rep — the kv-head broadcast happens in the BlockSpec
    index map (no materialised repeat)."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    grid = (bh, pl.cdiv(sq, block_q))
    # trace under x64-off: the framework enables global x64 (paddle's
    # int64 default), which makes index-map literals trace as i64 —
    # Mosaic only legalizes i32, and everything in these kernels is
    # explicitly typed anyway
    with jax.enable_x64(False):
        out, lse = _fwd_call(q, k, v, scale, causal, block_q, block_k,
                             interpret, bh, sq, sk, d, grid, q_offset,
                             n_rep)
    return out, lse[..., 0]


def _kv_row(n_rep):
    """GQA index map: q row b = batch*Hq + hq → kv row batch*Hkv + hq//rep
    (identity when n_rep == 1, since then Hq == Hkv)."""
    if n_rep == 1:
        return lambda b: b
    return lambda b: b // n_rep


@jax.named_scope("flash_fwd")
def _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret,
              bh, sq, sk, d, grid, q_offset, n_rep):
    kv_row = _kv_row(n_rep)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (kv_row(b), 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (kv_row(b), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale: float, causal: bool, block_q: int,
                   block_k: int, seq_k: int, q_offset: int = 0):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                   # [BQ, 1]
    delta = delta_ref[0]                               # [BQ, 1]
    bq, d = q.shape

    hi = (jnp.int32(seq_k) if not causal
          else jnp.int32(q_offset) + (qi + 1) * jnp.int32(block_q))
    nblocks = pl.cdiv(hi, jnp.int32(block_k))

    def body(j, dq):
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        if causal:
            q_idx = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_idx = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nblocks, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale: float, causal: bool,
                    block_q: int, block_k: int, seq_q: int,
                    q_offset: int = 0, n_rep: int = 1):
    """dk/dv for one kv block.  With n_rep > 1 (GQA) the grid carries a
    trailing rep axis: grid step (b, ki, r) processes the r-th q head
    sharing this kv head, ACCUMULATING into the same dk/dv output block
    (initialised at r == 0) — the canonical Pallas revisiting pattern."""
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape

    lo = (jnp.int32(0) if not causal
          else jnp.maximum(
              (ki * jnp.int32(block_k) - jnp.int32(q_offset)), 0)
          // jnp.int32(block_q))
    nblocks = pl.cdiv(jnp.int32(seq_q), jnp.int32(block_q))

    def body(i, carry):
        dk, dv = carry

        def compute(carry):
            dk, dv = carry
            q_blk = q_ref[0, pl.ds(i * block_q, block_q), :].astype(
                jnp.float32) * scale
            do_blk = do_ref[0, pl.ds(i * block_q, block_q), :].astype(
                jnp.float32)
            lse = lse_ref[0, pl.ds(i * block_q, block_q), :]    # [BQ, 1]
            delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
            s = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32)
            if causal:
                q_idx = q_offset + i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 0)
                k_idx = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 1)
                s = jnp.where(q_idx >= k_idx, s, NEG_INF)
            p = jnp.exp(s - lse)                      # [BQ, BK]
            dv_new = dv + jnp.dot(p.T, do_blk,
                                  preferred_element_type=jnp.float32)
            dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dk_new = dk + jnp.dot(ds.T, q_blk,
                                  preferred_element_type=jnp.float32)
            return dk_new, dv_new

        if causal:
            return jax.lax.cond(i >= lo, compute, lambda c: c, carry)
        return compute(carry)

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nblocks, body, (dk0, dv0))
    if n_rep == 1:
        dk_ref[0] = dk.astype(dk_ref.dtype)  # q already carried `scale`
        dv_ref[0] = dv.astype(dv_ref.dtype)
    else:
        # cross-rep accumulation: the out refs are fp32 (the caller casts
        # once after the call) so the n_rep partial sums never round in
        # the storage dtype
        rep_i = pl.program_id(2)

        @pl.when(rep_i == 0)
        def _init():
            dk_ref[0] = dk
            dv_ref[0] = dv

        @pl.when(rep_i > 0)
        def _acc():
            dk_ref[0] += dk
            dv_ref[0] += dv


def _flash_bwd(q, k, v, out, lse, do, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool,
               q_offset: int = 0, n_rep: int = 1):
    bh, sq, d = q.shape
    bhkv, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                           # [BH, SQ]
    # 3-D (bh, sq, 1) buffers for the same Mosaic tiling reason as fwd
    lse3 = lse[..., None]
    delta3 = delta[..., None]
    with jax.enable_x64(False):   # see _flash_fwd
        return _bwd_calls(q, k, v, do, lse3, delta3, scale, causal,
                          block_q, block_k, interpret, bh, bhkv, sq, sk,
                          d, q_offset, n_rep)


def _bwd_calls(q, k, v, do, lse3, delta3, scale, causal, block_q, block_k,
               interpret, bh, bhkv, sq, sk, d, q_offset, n_rep):
    kv_row = _kv_row(n_rep)
    with jax.named_scope("flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, seq_k=sk,
                              q_offset=q_offset),
            grid=(bh, pl.cdiv(sq, block_q)),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, sk, d), lambda b, i: (kv_row(b), 0, 0)),
                pl.BlockSpec((1, sk, d), lambda b, i: (kv_row(b), 0, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            interpret=interpret,
        )(q, k, v, do, lse3, delta3)

    if n_rep == 1:
        grid = (bhkv, pl.cdiv(sk, block_k))
        q_row = lambda b, j: b
        kv_idx = lambda b, j: (b, j, 0)
    else:
        # trailing rep axis iterates the q heads sharing each kv head;
        # dk/dv revisit their (b, j) block and accumulate (see kernel)
        grid = (bhkv, pl.cdiv(sk, block_k), n_rep)
        q_row = lambda b, j, r: b * n_rep + r
        kv_idx = lambda b, j, r: (b, j, 0)
    q_idx = lambda b, j, *r: (q_row(b, j, *r), 0, 0)
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, seq_q=sq,
                              q_offset=q_offset, n_rep=n_rep),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, sq, d), q_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, sq, d), q_idx),
                pl.BlockSpec((1, sq, 1), q_idx),
                pl.BlockSpec((1, sq, 1), q_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
            ],
            out_shape=[
                # fp32 outputs under GQA: the rep-axis revisiting accumulation
                # must not round per-add in bf16 (cast once below instead)
                jax.ShapeDtypeStruct((bhkv, sk, d),
                                     jnp.float32 if n_rep > 1 else k.dtype),
                jax.ShapeDtypeStruct((bhkv, sk, d),
                                     jnp.float32 if n_rep > 1 else v.dtype),
            ],
            interpret=interpret,
        )(q, k, v, do, lse3, delta3)
    if n_rep > 1:
        dk = dk.astype(k.dtype)
        dv = dv.astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (jnp level — the tape's jax.vjp picks this up)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_bhsd(q, k, v, scale: float, causal: bool,
                         block_q: int = DEFAULT_BLOCK_Q,
                         block_k: int = DEFAULT_BLOCK_K,
                         interpret: bool = False,
                         q_offset: int = 0, n_rep: int = 1):
    """Flash attention over [B*H, S, D] tensors.

    - ``q_offset``: global position of q row 0 under causal masking —
      bottom-right alignment for decode steps (sq < sk, offset sk - sq).
    - ``n_rep``: GQA — q has n_rep heads per kv head ([B*Hq, SQ, D] vs
      [B*Hkv, SK, D]); the broadcast lives in BlockSpec index maps and
      the dk/dv accumulation grid, never materialised."""
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                        interpret, q_offset, n_rep)
    return out


def _fa_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
            q_offset, n_rep):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret, q_offset, n_rep)
    return out, (q, k, v, out, lse)


def _fa_bwd(scale, causal, block_q, block_k, interpret, q_offset, n_rep,
            res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, scale, causal,
                            block_q, block_k, interpret, q_offset, n_rep)
    return dq, dk, dv


flash_attention_bhsd.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# jnp reference (used by tests and as the non-TPU fallback path)
# ---------------------------------------------------------------------------

def reference_attention_bhsd(q, k, v, scale: float, causal: bool):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)
