"""Ragged paged attention — ONE kernel launch for a mixed
prefill/decode serving batch (PAPERS.md: *Ragged Paged Attention*,
arXiv 2604.15464).

The serving engine's step batch is ragged twice over: each sequence
contributes a different number of NEW query tokens this iteration
(a fresh request prefills its whole prompt chunk, an ongoing request
decodes exactly one token), and each sequence's KV context is a
different length scattered across fixed-size cache pages.  The
reference ecosystem serves this with block_multihead_attention +
separate prefill/decode kernels; the TPU-native shape is a single
launch whose grid walks (sequence, page) with the per-sequence
lengths and page tables riding as scalar-prefetch refs — the index
maps pick each sequence's OWN pages out of the shared pool, and pages
past a sequence's length are skipped under ``pl.when``, so the dot-
product FLOPs of wildly different context lengths cost only their own
pages.  (The grid itself is still statically ``(B, ppseq)``: the
skipped steps pay their block copies but no compute.)

Layout:

* ``q [B, Q, nh, hd]`` — per-sequence query chunks, padded to the
  batch's widest chunk ``Q`` (decode rows use 1 of it, prefill rows up
  to all of it).  Query token ``i`` of sequence ``b`` sits at absolute
  position ``kv_lens[b] - q_lens[b] + i``.
* ``k_pages/v_pages [nkv, P, ps, hd]`` — the shared page pools, new
  tokens already appended (the engine scatters k/v BEFORE attending,
  mirroring ``attend_cache_append``).
* ``kv_lens i32[B]`` — post-append context lengths; ``q_lens i32[B]``
  — valid query rows; ``page_tables i32[B, ppseq]`` — each sequence's
  page ids (slots past its length may point anywhere mapped; they are
  masked by ``kv_lens``).

Returns ``[B, Q, nh, hd]``; rows ``i >= q_lens[b]`` are padding and
undefined (finite, never NaN — a zero-context row is exactly zero).

The kernel runs online softmax across a sequence's pages (running
max / denominator / accumulator in VMEM scratch, masked probabilities
so fully-masked pages contribute nothing), with GQA as a static
per-kv-head loop like ``fused_decode.attend_cache_append``.  The jnp
reference below is the numerics oracle (fp32 logits, ``-1e30`` mask
constant — the eager sdpa constants) and the route everywhere the
kernel is not available.  PTL603 applies: every constructor literal is
pinned 32-bit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...flags import get_flag
from . import kernel_enabled

__all__ = ["ragged_paged_attention", "ragged_paged_attention_ref",
           "append_positions", "available"]


def append_positions(kv_lens, tables, live, page_size, sink):
    """On-device page-append cursors for ONE decode token per lane:
    where lane ``b``'s next k/v row lands given its current ``kv_lens
    [B]`` and ``tables [B, ppseq]``.  Returns ``(page_ids [B], slots
    [B])`` int32; lanes with ``live`` False target the ``sink`` page at
    slot 0 (written, never read back — the engine's padding-lane
    contract).  Pure jnp so the fused serving window can re-derive the
    cursors inside its compiled loop body instead of reading them from
    the host every iteration."""
    kv = kv_lens.astype(jnp.int32)
    lanes = jnp.arange(kv.shape[0], dtype=jnp.int32)
    ps = jnp.int32(page_size)
    page_ids = jnp.where(live, tables[lanes, kv // ps], jnp.int32(sink))
    slots = jnp.where(live, kv % ps, jnp.int32(0))
    return page_ids, slots


def available() -> bool:
    return kernel_enabled("use_pallas_ragged_attention")


def _interpret() -> bool:
    return bool(get_flag("pallas_interpret"))


# ---------------------------------------------------------------------------
# jnp reference (the oracle + the non-TPU route)
# ---------------------------------------------------------------------------

def ragged_paged_attention_ref(q, k_pages, v_pages, kv_lens, q_lens,
                               page_tables, scale=None):
    """Dense-gather reference: collect each sequence's pages, run
    masked attention with the ragged causal alignment.  Shapes as in
    the module docstring; pure jnp, differentiable, used as the
    route whenever the kernel is unavailable."""
    b, qw, nh, hd = q.shape
    nkv, _, ps, _ = k_pages.shape
    rep = nh // nkv
    ppseq = page_tables.shape[1]
    t = ppseq * ps
    sc = jnp.float32(scale if scale is not None
                     else 1.0 / math.sqrt(hd))
    kv_lens = kv_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    # [B, nkv, T, hd] gathered per sequence, GQA-broadcast to nh
    k = jnp.swapaxes(k_pages[:, page_tables], 0, 1) \
        .reshape(b, nkv, t, hd)
    v = jnp.swapaxes(v_pages[:, page_tables], 0, 1) \
        .reshape(b, nkv, t, hd)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)       # [B, nh, Q, hd]
    logits = jnp.einsum("bhqd,bhtd->bhqt", qt,
                        k.astype(jnp.float32)) * sc
    kvpos = jnp.arange(t, dtype=jnp.int32)               # [T]
    qpos = (kv_lens - q_lens)[:, None] \
        + jnp.arange(qw, dtype=jnp.int32)[None, :]       # [B, Q]
    mask = (kvpos[None, None, :] <= qpos[:, :, None]) \
        & (kvpos[None, None, :] < kv_lens[:, None, None])  # [B, Q, T]
    logits = jnp.where(mask[:, None], logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1)
    # a row with no attendable position (padding slots) is zeros, not
    # softmax-over-all-masked garbage — same contract as paged_attention
    probs = jnp.where(jnp.any(mask, axis=-1)[:, None, :, None], probs,
                      jnp.float32(0.0))
    ctx = jnp.einsum("bhqt,bhtd->bhqd", probs,
                     v.astype(jnp.float32))
    return jnp.swapaxes(ctx, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

# Scoped VMEM is 16 MiB on v5e; the q tile is sized to use at most half
# of it, leaving the rest to the k/v page blocks and Mosaic's own temps.
_VMEM_TILE_BUDGET = 8 << 20
_MAX_BLOCK_Q = 128


def _block_q(nh: int, hd: int, itemsize: int) -> int:
    """Query rows per grid tile: the largest power of two (8..128)
    whose VMEM residents fit ``_VMEM_TILE_BUDGET`` at this model's
    ``(nh, hd, dtype)``.  Per (head, row) the kernel holds the q and
    out blocks double-buffered (4 x itemsize), the fp32 accumulator and
    the fp32 copy of q (8 bytes) over ``hd`` padded to the 128-lane
    tile, plus the lane-padded ``[rows, 1]`` max/denominator scratch
    and the ``[rows, page]`` logits/probabilities (~6 fp32 lane rows)."""
    lanes = -(-hd // 128) * 128
    per_row = nh * (lanes * (4 * itemsize + 8) + 6 * 128 * 4)
    bq = _MAX_BLOCK_Q
    while bq > 8 and bq * per_row > _VMEM_TILE_BUDGET:
        bq //= 2
    return bq


def _ragged_kernel(kv_lens_ref, q_lens_ref, tables_ref, q_ref, k_ref,
                   v_ref, o_ref, acc_ref, m_ref, d_ref, *, n_kv: int,
                   n_rep: int, block_q: int, page_size: int,
                   pages_per_seq: int, scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)
    nh = n_kv * n_rep
    rows = nh * block_q

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, jnp.float32(-1e30))
        d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    q0 = jnp.int32(block_q) * t          # first query row of this tile
    page0 = jnp.int32(page_size) * p     # first kv position of the page

    # skip the dot products of (a) pages at or past ceil(kv_len / ps)
    # (their table entries fetch page 0, fully masked), (b) query tiles
    # past q_len (pure padding — a decode lane in a prefill-wide step
    # computes one tile) and (c) pages wholly above the tile's last
    # causal position — so compute scales with the sequence's OWN
    # lengths, not the padded maxima
    @pl.when((page0 < kv_len) & (q0 < q_len)
             & (page0 < kv_len - q_len + q0 + jnp.int32(block_q)))
    def _compute():
        # [rows, ps] index planes: query row i of head h sits at flat
        # row h*block_q + i; its absolute position is kv_len - q_len +
        # q0 + i
        qi = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) % jnp.int32(block_q)
        kvpos = page0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qpos = kv_len - q_len + qi
        mask = (kvpos <= qpos) & (kvpos < kv_len)
        # the wrapper hands q heads-major with block_q a multiple of
        # the 8-sublane tile, so this collapse is layout-trivial
        qf = q_ref[0].astype(jnp.float32).reshape(rows, -1)
        for g in range(n_kv):                            # static GQA loop
            sl = slice(g * n_rep * block_q, (g + 1) * n_rep * block_q)
            kg = k_ref[g, 0].astype(jnp.float32)         # [ps, hd]
            vg = v_ref[g, 0].astype(jnp.float32)
            s = jax.lax.dot_general(qf[sl], kg,
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * jnp.float32(scale)
            s = jnp.where(mask[sl], s, jnp.float32(-1e30))
            m_prev = m_ref[sl]                           # [rows_g, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # masked probabilities: a fully-masked page must
            # contribute 0, not exp(-1e30 - (-1e30)) == 1
            prob = jnp.where(mask[sl], jnp.exp(s - m_new),
                             jnp.float32(0.0))
            d_ref[sl] = d_ref[sl] * alpha \
                + jnp.sum(prob, axis=-1, keepdims=True)
            acc_ref[sl] = acc_ref[sl] * alpha \
                + jax.lax.dot_general(prob, vg,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            m_ref[sl] = m_new

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        d = d_ref[...]
        out = jnp.where(d > jnp.float32(0.0), acc_ref[...] / d,
                        jnp.float32(0.0))
        o_ref[0] = out.reshape(nh, block_q, -1).astype(o_ref.dtype)


def _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens, page_tables,
                   scale):
    b, qw, nh, hd = q.shape
    nkv, _, ps, _ = k_pages.shape
    ppseq = page_tables.shape[1]
    # Mosaic tiles the second-minor dim by 8 sublanes: a decode step's
    # one-row chunk is padded up to a whole tile, and a wide prefill
    # chunk is cut into block_q-row tiles along a grid axis so VMEM
    # holds one tile, not the whole chunk
    bq = min(_block_q(nh, hd, q.dtype.itemsize), -(-qw // 8) * 8)
    qp = -(-qw // bq) * bq
    # heads-major [B, nh, Q, hd]: the kernel collapses (nh, block_q)
    # into flat rows without an in-kernel transpose
    qt = jnp.swapaxes(q, 1, 2)
    if qp != qw:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, qp - qw), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, qp // bq, ppseq),
        in_specs=[
            pl.BlockSpec((1, nh, bq, hd),
                         lambda i, t, p, kl, ql, tb: (i, 0, t, 0)),
            pl.BlockSpec((nkv, 1, ps, hd),
                         lambda i, t, p, kl, ql, tb: (0, tb[i, p], 0, 0)),
            pl.BlockSpec((nkv, 1, ps, hd),
                         lambda i, t, p, kl, ql, tb: (0, tb[i, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh, bq, hd),
                               lambda i, t, p, kl, ql, tb: (i, 0, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh * bq, hd), jnp.float32),   # acc
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # running max
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # denominator
        ],
    )
    with jax.enable_x64(False), jax.named_scope("ragged_paged_attn"):
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, n_kv=nkv,
                              n_rep=nh // nkv, block_q=bq,
                              page_size=ps, pages_per_seq=ppseq,
                              scale=float(scale)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, nh, qp, hd), q.dtype),
            interpret=_interpret(),
        )(kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
          page_tables.astype(jnp.int32), qt, k_pages, v_pages)
    return jnp.swapaxes(out[:, :, :qw], 1, 2)


def ragged_paged_attention(q, k_pages, v_pages, kv_lens, q_lens,
                           page_tables, scale=None):
    """One-launch mixed prefill/decode attention over paged KV.

    ``q [B, Q, nh, hd]`` (per-sequence chunks padded to ``Q``);
    ``k/v_pages [nkv, P, ps, hd]``; ``kv_lens/q_lens i32[B]``;
    ``page_tables i32[B, ppseq]`` → ``[B, Q, nh, hd]``.  Routes to the
    Pallas kernel when available (TPU, or CPU interpret mode), else the
    jnp reference — both produce the eager sdpa numerics on the valid
    rows (``i < q_lens[b]``)."""
    hd = q.shape[-1]
    nh, nkv = q.shape[2], k_pages.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if available() and nh % nkv == 0 and hd % 8 == 0:
        return _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens,
                              page_tables, scale)
    return ragged_paged_attention_ref(q, k_pages, v_pages, kv_lens,
                                      q_lens, page_tables, scale)
