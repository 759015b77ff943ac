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
pages.  The grid is static, so a step the lengths rule out still costs
its turn: the causal walk therefore takes ``_KEYS_PER_STEP`` keys a grid
step (``128 // page_size`` pages, each its own block of the pool), and
its index maps stop at the last block a query tile can see, so that the
steps past it name the block already held and copy nothing.

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

Three optional extensions, each off by default and each leaving the
plain causal call exactly the program it was:

* values narrower than keys — ``v_pages [nkv, P, ps, hdv]`` with
  ``hdv != hd``; the result is then ``[B, Q, nh, hdv]``;
* ``sinks f32[nh]`` — a learned logit per query head that joins the
  softmax's denominator and carries no value:
  ``p_j = exp(a_j) / (sum_visible exp(a_k) + exp(s_h))``.  In the
  kernel it is the running max's and denominator's initial state;
* ``window W`` — key ``j`` is visible to the query at position ``i``
  iff ``i - W < j <= i``.  The grid then walks only the
  ``_window_pages`` pages a query tile's window can reach, starting at
  page ``(kv_len - q_len + q0 - W + 1) // ps``, whatever the context's
  length.  The page table handed with a window is read as a ring of
  ``R = ppseq`` pages in which position ``p`` lives in entry
  ``(p // ps) % R`` (the serving engine's window layers keep
  ``R * ps >= W + chunk`` positions a lane and no more; a table that
  holds the whole sequence is the ring that never wraps).

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
                               page_tables, scale=None, window=None,
                               sinks=None):
    """Dense-gather reference: collect each sequence's pages, run
    masked attention with the ragged causal alignment.  Shapes as in
    the module docstring; pure jnp, differentiable, used as the
    route whenever the kernel is unavailable.  With a ``window`` each
    query row gathers its own ``W`` key positions through the ring, so
    the cost is ``B * Q * W`` whatever the context."""
    b, qw, nh, hd = q.shape
    nkv, _, ps, _ = k_pages.shape
    hdv = v_pages.shape[-1]
    rep = nh // nkv
    ppseq = page_tables.shape[1]
    sc = jnp.float32(scale if scale is not None
                     else 1.0 / math.sqrt(hd))
    kv_lens = kv_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)       # [B, nh, Q, hd]
    qpos = (kv_lens - q_lens)[:, None] \
        + jnp.arange(qw, dtype=jnp.int32)[None, :]       # [B, Q]
    if window is None:
        t = ppseq * ps
        # [B, nkv, T, hd] gathered per sequence, GQA-broadcast to nh
        k = jnp.swapaxes(k_pages[:, page_tables], 0, 1) \
            .reshape(b, nkv, t, hd)
        v = jnp.swapaxes(v_pages[:, page_tables], 0, 1) \
            .reshape(b, nkv, t, hdv)
        if rep > 1:
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        logits = jnp.einsum("bhqd,bhtd->bhqt", qt,
                            k.astype(jnp.float32)) * sc
        kvpos = jnp.arange(t, dtype=jnp.int32)           # [T]
        mask = (kvpos[None, None, :] <= qpos[:, :, None]) \
            & (kvpos[None, None, :] < kv_lens[:, None, None])  # [B, Q, T]
        spec = "bhqt,bhtd->bhqd"
    else:
        w = int(window)
        # key positions of each query row: [B, Q, W], oldest first
        kvpos = qpos[:, :, None] - jnp.int32(w - 1) \
            + jnp.arange(w, dtype=jnp.int32)[None, None, :]
        mask = (kvpos >= 0) & (kvpos < kv_lens[:, None, None]) \
            & (jnp.arange(qw, dtype=jnp.int32)[None, :, None]
               < q_lens[:, None, None])
        safe = jnp.maximum(kvpos, jnp.int32(0))
        entry = (safe // jnp.int32(ps)) % jnp.int32(ppseq)
        pages = jnp.take_along_axis(page_tables.astype(jnp.int32),
                                    entry.reshape(b, qw * w), axis=1) \
            .reshape(b, qw, w)
        slot = safe % jnp.int32(ps)
        k = k_pages[:, pages, slot]                      # [nkv, B, Q, W, hd]
        v = v_pages[:, pages, slot]
        if rep > 1:
            k = jnp.repeat(k, rep, axis=0)
            v = jnp.repeat(v, rep, axis=0)
        logits = jnp.einsum("bhqd,hbqtd->bhqt", qt,
                            k.astype(jnp.float32)) * sc
        spec = "bhqt,hbqtd->bhqd"
    logits = jnp.where(mask[:, None], logits, jnp.float32(-1e30))
    if sinks is not None:
        # the sink is one more column of the softmax, dropped after it
        col = jnp.broadcast_to(
            sinks.astype(jnp.float32)[None, :, None, None],
            logits.shape[:3] + (1,))
        probs = jax.nn.softmax(jnp.concatenate([logits, col], axis=-1),
                               axis=-1)[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    # a row with no attendable position (padding slots) is zeros, not
    # softmax-over-all-masked garbage — same contract as paged_attention
    probs = jnp.where(jnp.any(mask, axis=-1)[:, None, :, None], probs,
                      jnp.float32(0.0))
    ctx = jnp.einsum(spec, probs, v.astype(jnp.float32))
    return jnp.swapaxes(ctx, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# the Pallas kernel
# ---------------------------------------------------------------------------

# The kernel asks for 64 of the v5e's 128 MiB of VMEM (Mosaic's default
# scope is 16) and sizes the q tile to at most half of that, leaving the
# rest to the k/v page blocks and Mosaic's own temps.  The grid's cost is
# its steps, lanes x tiles x page steps, whether they compute or skip:
# a tile four times as tall is a quarter of them.
_VMEM_LIMIT = 64 << 20
_VMEM_TILE_BUDGET = _VMEM_LIMIT // 2
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


def _window_pages(rows: int, window: int, page_size: int) -> int:
    """Pages that ``rows`` consecutive queries with a window of
    ``window`` keys each can reach: ``rows + window - 1`` consecutive
    positions starting anywhere inside a page."""
    span = rows + window - 1
    return (span + page_size - 2) // page_size + 1


# Keys a grid step of the causal walk attends.  A step costs its turn
# whether it computes or skips (on a v5e ~0.26 us with one k and one v
# block, ~1.2 us with eight of each), and a chunk of 1,024 rows over a
# table of 512 pages was half a million steps a page at a time; 128 keys
# are one MXU tile's width and an eighth of those steps at pages of 16.
_KEYS_PER_STEP = 128


def _last_block(kv_len, q_len, q0, block_q: int, keys: int):
    """The last block of ``keys`` positions the query tile starting at
    row ``q0`` can see: the one that holds its last row's own position,
    inside the context; block 0 for a tile of padding rows."""
    top = jnp.minimum(kv_len - q_len + q0 + jnp.int32(block_q), kv_len)
    top = jnp.where(q0 < q_len, jnp.maximum(top - 1, 0), 0)
    return top // jnp.int32(keys)


def _first_page(kv_len, q_len, q0, window: int, page_size: int):
    """The page that holds the oldest key the query tile starting at
    row ``q0`` can see."""
    oldest = kv_len - q_len + q0 - jnp.int32(window - 1)
    return jnp.maximum(oldest, jnp.int32(0)) // jnp.int32(page_size)


def _ragged_kernel(kv_lens_ref, q_lens_ref, tables_ref, q_ref, *rest,
                   n_kv: int, n_rep: int, block_q: int, page_size: int,
                   group: int, n_pages: int, scale: float, window,
                   has_sink: bool, precision=None):
    # ``group`` consecutive pages a grid step, each a block of its own;
    # ``n_pages`` grid steps along the page axis
    k_refs, v_refs = rest[:group], rest[group:2 * group]
    if has_sink:
        sink_ref, o_ref, acc_ref, m_ref, d_ref = rest[2 * group:]
    else:
        o_ref, acc_ref, m_ref, d_ref = rest[2 * group:]
    keys = group * page_size
    b = pl.program_id(0)
    t = pl.program_id(1)
    p = pl.program_id(2)
    nh = n_kv * n_rep
    rows = nh * block_q

    @pl.when(p == 0)
    def _init():
        if has_sink:
            # the sink is a key with no value: the running max starts at
            # its logit and the denominator at exp(0)
            m_ref[...] = sink_ref[...]
            d_ref[...] = jnp.ones_like(d_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, jnp.float32(-1e30))
            d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    q0 = jnp.int32(block_q) * t          # first query row of this tile
    if window is None:
        page = jnp.int32(group) * p
    else:
        page = _first_page(kv_len, q_len, q0, window, page_size) + p
    page0 = jnp.int32(page_size) * page  # first kv position of the step

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
            jnp.int32, (rows, keys), 0) % jnp.int32(block_q)
        kvpos = page0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        qpos = kv_len - q_len + qi
        mask = (kvpos <= qpos) & (kvpos < kv_len)
        if window is not None:
            mask = mask & (kvpos > qpos - jnp.int32(window))
        # the wrapper hands q heads-major with block_q a multiple of
        # the 8-sublane tile, so this collapse is layout-trivial
        qf = q_ref[0].astype(jnp.float32).reshape(rows, -1)
        for g in range(n_kv):                            # static GQA loop
            sl = slice(g * n_rep * block_q, (g + 1) * n_rep * block_q)
            kg, vg = (jnp.concatenate([r[g, 0] for r in refs], axis=0)
                      .astype(jnp.float32)
                      for refs in (k_refs, v_refs))      # [keys, hd]
            s = jax.lax.dot_general(qf[sl], kg,
                                    (((1,), (1,)), ((), ())),
                                    precision=precision,
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
                                      precision=precision,
                                      preferred_element_type=jnp.float32)
            m_ref[sl] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        d = d_ref[...]
        out = jnp.where(d > jnp.float32(0.0), acc_ref[...] / d,
                        jnp.float32(0.0))
        o_ref[0] = out.reshape(nh, block_q, -1).astype(o_ref.dtype)


def _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens, page_tables,
                   scale, window=None, sinks=None, precision=None):
    """The kernel's launch, as a jitted function of its own: a step
    calls it once a layer, and the layers of one geometry then share
    one trace and one lowering to Mosaic (a lowering costs ~0.3 s of
    set-up with the causal walk's sixteen page blocks, a program at a
    time, cached or not)."""
    return _ragged_call(q, k_pages, v_pages, kv_lens, q_lens, page_tables,
                        sinks, scale=float(scale),
                        window=None if window is None else int(window),
                        precision=precision, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "window", "precision",
                                             "interpret"))
def _ragged_call(q, k_pages, v_pages, kv_lens, q_lens, page_tables, sinks,
                 *, scale, window, precision, interpret):
    b, qw, nh, hd = q.shape
    nkv, _, ps, _ = k_pages.shape
    hdv = v_pages.shape[-1]
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
    if window is None:
        group = max(1, _KEYS_PER_STEP // ps)
        n_pages = -(-ppseq // group)
        if n_pages * group != ppseq:
            # whole steps: the added entries lie past every context
            page_tables = jnp.pad(
                page_tables, ((0, 0), (0, n_pages * group - ppseq)),
                mode="edge")

        def kv_map(j):
            def index(i, t, p, kl, ql, tb):
                # past the tile's last block the step names the block it
                # holds, so a step that computes nothing copies nothing
                last = _last_block(kl[i], ql[i], jnp.int32(bq) * t, bq,
                                   group * ps)
                return (0, tb[i, jnp.minimum(p, last) * jnp.int32(group)
                              + jnp.int32(j)], 0, 0)
            return index
    else:
        # only the pages the tile's window reaches, one a step; a tile
        # holds at most min(bq, qw) real rows
        group = 1
        n_pages = min(_window_pages(min(bq, qw), window, ps), ppseq)

        def kv_map(j):
            def index(i, t, p, kl, ql, tb):
                entry = _first_page(kl[i], ql[i], jnp.int32(bq) * t,
                                    window, ps) + p
                return (0, tb[i, entry % jnp.int32(ppseq)], 0, 0)
            return index

    def q_map(i, t, p, kl, ql, tb):
        return (i, 0, t, 0)

    in_specs = [pl.BlockSpec((1, nh, bq, hd), q_map)] \
        + [pl.BlockSpec((nkv, 1, ps, hd), kv_map(j)) for j in range(group)] \
        + [pl.BlockSpec((nkv, 1, ps, hdv), kv_map(j)) for j in range(group)]
    operands = [qt] + [k_pages] * group + [v_pages] * group
    if sinks is not None:
        # one logit per flat (head, row) of the tile
        in_specs.append(pl.BlockSpec(
            (nh * bq, 1), lambda i, t, p, kl, ql, tb: (0, 0)))
        operands.append(jnp.repeat(sinks.astype(jnp.float32), bq)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, qp // bq, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, bq, hdv), q_map),
        scratch_shapes=[
            pltpu.VMEM((nh * bq, hdv), jnp.float32),  # acc
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # running max
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # denominator
        ],
    )
    name = "ragged_paged_attn" if window is None \
        else "ragged_paged_attn_window"
    with jax.enable_x64(False), jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, n_kv=nkv,
                              n_rep=nh // nkv, block_q=bq,
                              page_size=ps, group=group,
                              n_pages=n_pages, scale=scale,
                              window=window,
                              has_sink=sinks is not None,
                              precision=precision),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, nh, qp, hdv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32),
          page_tables.astype(jnp.int32), *operands)
    return jnp.swapaxes(out[:, :, :qw], 1, 2)


def ragged_paged_attention(q, k_pages, v_pages, kv_lens, q_lens,
                           page_tables, scale=None, window=None,
                           sinks=None, precision=None):
    """One-launch mixed prefill/decode attention over paged KV.

    ``q [B, Q, nh, hd]`` (per-sequence chunks padded to ``Q``);
    ``k_pages [nkv, P, ps, hd]``, ``v_pages [nkv, P, ps, hdv]``;
    ``kv_lens/q_lens i32[B]``; ``page_tables i32[B, ppseq]`` →
    ``[B, Q, nh, hdv]``.  ``window`` and ``sinks f32[nh]`` as in
    the module docstring; ``precision`` is that of the kernel's two
    dot products (None: Mosaic's default, one bf16 pass of float32
    operands; ``jax.lax.Precision.HIGHEST``: float32 products — Mosaic
    takes no "high").  Routes to the Pallas kernel when
    available (TPU, or CPU interpret mode), else the jnp reference —
    both produce the eager sdpa numerics on the valid rows
    (``i < q_lens[b]``)."""
    hd = q.shape[-1]
    nh, nkv = q.shape[2], k_pages.shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if available() and nh % nkv == 0 and hd % 8 == 0 \
            and v_pages.shape[-1] % 8 == 0:
        return _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens,
                              page_tables, scale, window, sinks,
                              precision)
    return ragged_paged_attention_ref(q, k_pages, v_pages, kv_lens,
                                      q_lens, page_tables, scale, window,
                                      sinks)
