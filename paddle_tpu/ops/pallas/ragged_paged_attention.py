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
launch whose grid walks the step's LIVE QUERY TILES — ``block_q`` rows
of one sequence each, a sequence's tiles one behind the other — with the
per-sequence lengths, the page tables and the tile list (tile -> its
sequence and its first row) riding as scalar-prefetch refs.  The tile
list is made inside the jitted launch from ``q_lens`` alone, and its
length is static: ``min(B * ceil(Q / block_q), ceil(rows / block_q) +
B)`` slots, of which those past the live tiles start no copy and write
zeros.  The page pools stay in HBM, and the walk over a sequence's keys
is a loop INSIDE the kernel: a tile copies its sequence's OWN pages,
named by its row of the page table, a block of ``_KEYS_PER_STEP`` keys
at a time into one of two VMEM buffers (the next block's copies start
before the current block is waited for), from the sequence's first key
to the block that holds the tile's last row's own position and no
further — so copies and dot-product FLOPs of wildly different context
lengths cost only their own pages, and a step of one wide chunk beside
decoding lanes costs the tiles of its own rows: no array of q, of the
output or of anything else is ``B x Q`` rows tall.

Layout (:func:`ragged_paged_attention_rows`, the serving step's call):

* ``q [rows, nh, hd]`` — the step's PACKED rows: sequence ``b`` owns
  rows ``offs[b] .. offs[b] + q_lens[b] - 1`` (``offs i32[B]``
  nondecreasing; decode sequences own 1 row, a prefill chunk up to the
  static ``q_width``), the rest carry no token.  Query token ``i`` of
  sequence ``b`` sits at absolute position ``kv_lens[b] - q_lens[b] +
  i``.  The launch gathers the rows into ``[slots, nh, block_q, hd]``
  tiles (heads-major a tile) and its ``[slots, nh, block_q, hdv]``
  output back into rows: ``slots * block_q`` rows each way.
* ``k_pages/v_pages [nkv, P, ps, hd]`` — the shared page pools, new
  tokens already appended (the engine scatters k/v BEFORE attending,
  mirroring ``attend_cache_append``).
* ``kv_lens i32[B]`` — post-append context lengths; ``q_lens i32[B]``
  — valid query rows; ``page_tables i32[B, ppseq]`` — each sequence's
  page ids (slots past its length may point anywhere mapped; they are
  masked by ``kv_lens``).

Returns ``[rows, nh, hd]``; a row that carries no token is exactly zero.
:func:`ragged_paged_attention` is the same launch for ``q [B, Q, nh,
hd]`` (per-sequence chunks padded to the batch's widest chunk ``Q``:
``offs[b] = b * Q``) and the twin of the jnp oracle; it returns ``[B, Q,
nh, hd]`` with rows ``i >= q_lens[b]`` zero.

Three optional extensions, each off by default and each leaving the
plain causal call exactly the program it was:

* values narrower than keys — ``v_pages [nkv, P, ps, hdv]`` with
  ``hdv != hd``; the result's last axis is then ``hdv``;
* ``sinks f32[nh]`` — a learned logit per query head that joins the
  softmax's denominator and carries no value:
  ``p_j = exp(a_j) / (sum_visible exp(a_k) + exp(s_h))``.  In the
  kernel it is the running max's and denominator's initial state;
* ``window W`` — key ``j`` is visible to the query at position ``i``
  iff ``i - W < j <= i``.  A tile then copies only the
  ``_window_pages`` pages its window can reach, starting at page
  ``(kv_len - q_len + q0 - W + 1) // ps``, whatever the context's
  length, side by side into one buffer, and attends them in ONE pass
  (up to ``_MAX_WINDOW_KEYS`` keys; a wider window is walked in blocks
  of that many).  The page table handed with a window is read as a
  ring of ``R = ppseq`` pages in which position ``p`` lives in entry
  ``(p // ps) % R`` (the serving engine's window layers keep
  ``R * ps >= W + chunk`` positions a lane and no more; a table that
  holds the whole sequence is the ring that never wraps).

The kernel runs online softmax across a sequence's blocks (running
max / denominator / accumulator in VMEM scratch, masked probabilities
so fully-masked blocks contribute nothing), with GQA as a static
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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...flags import get_flag
from . import kernel_enabled

__all__ = ["ragged_paged_attention", "ragged_paged_attention_rows",
           "ragged_paged_attention_ref", "append_positions", "available"]


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
# rest to the two key/value buffers and Mosaic's own temps.  A grid step
# is a tile: a tile four times as tall is a quarter of them, and walks
# its keys once for four times the rows.
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
    and the ``[rows, keys]`` logits/probabilities (~6 fp32 lane rows)."""
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


# Keys a turn of the causal walk copies and attends: one block of
# ``256 // page_size`` pages, a copy a page a pool into one of two VMEM
# buffers.  A turn costs its copies' issue and, at "highest", the split
# of its q rows into bfloat16 parts whatever the block's width, so wider
# blocks are fewer of both; the last block of a walk is copied and
# multiplied whole however few of its keys are visible, so wider blocks
# waste more.  On a v5e (PR 32) 128 / 256 / 512 keys a turn took 70 / 74
# / 82 us a decode call at 32 heads of 128 over contexts of 128-1,150
# and 9.2 / 6.6 / 7.1 ms a 1,024-row call at 64 heads of 128 at
# "highest" over 4,096.
_KEYS_PER_STEP = 256
# The most keys a window layer's one pass holds at once; a window that
# reaches further is walked in blocks of this many, like a causal layer.
_MAX_WINDOW_KEYS = 512


def _last_block(kv_len, q_len, q0, block_q: int, keys: int, xp=jnp):
    """The last block of ``keys`` positions the query tile starting at
    row ``q0`` can see: the one that holds its last row's own position,
    inside the context; block 0 for a tile of padding rows.  ``xp``:
    ``jnp`` inside the kernel, ``numpy`` for the host's count."""
    top = xp.minimum(kv_len - q_len + q0 + xp.int32(block_q), kv_len)
    top = xp.where(q0 < q_len, xp.maximum(top - 1, 0), 0)
    return top // xp.int32(keys)


def _first_page(kv_len, q_len, q0, window: int, page_size: int, xp=jnp):
    """The page that holds the oldest key the query tile starting at
    row ``q0`` can see."""
    oldest = kv_len - q_len + q0 - xp.int32(window - 1)
    return xp.maximum(oldest, xp.int32(0)) // xp.int32(page_size)


def _walk(kv_len, q_len, q0, block_q: int, page_size: int, group: int,
          window, xp=jnp):
    """``(first page, blocks)`` of the walk of the query tile starting at
    row ``q0``: blocks of ``group`` pages from the page of the oldest key
    the tile can see (page 0 without a window) to the page of its last
    row's own position, and none for a tile of padding rows or an idle
    lane."""
    first = xp.int32(0) if window is None \
        else _first_page(kv_len, q_len, q0, window, page_size, xp)
    last = _last_block(kv_len, q_len, q0, block_q, page_size, xp)
    return first, xp.where(q0 < q_len,
                           (last - first) // xp.int32(group) + 1, 0)


def _tiling(qw: int, nh: int, hd: int, itemsize: int, page_size: int,
            ppseq: int, window):
    """``(rows a tile, pages a block)`` of a launch, from its shapes.
    Mosaic tiles the second-minor dim by 8 sublanes: a decode step's
    one-row chunk is padded up to a whole tile, and a wide prefill chunk
    is cut into block_q-row tiles along a grid axis so VMEM holds one
    tile, not the whole chunk.  A causal block is ``_KEYS_PER_STEP``
    keys; a window layer's is every page a tile's window can reach (a
    tile holds at most ``min(bq, qw)`` real rows), so that its walk is
    one pass, up to ``_MAX_WINDOW_KEYS``."""
    bq = min(_block_q(nh, hd, itemsize), -(-qw // 8) * 8)
    if window is None:
        return bq, max(1, _KEYS_PER_STEP // page_size)
    reach = min(_window_pages(min(bq, qw), window, page_size), ppseq)
    return bq, min(reach, max(1, _MAX_WINDOW_KEYS // page_size))


def walk_blocks(kv_lens, q_lens, qw: int, nh: int, hd: int, itemsize: int,
                page_size: int, ppseq: int, window=None) -> int:
    """The key blocks one launch walks, summed over lanes and tiles, by
    the kernel's own bounds (``_tiling``, ``_walk``) in numpy on the
    host: what the serving engine's ``attn_blocks`` counts a layer."""
    bq, group = _tiling(qw, nh, hd, itemsize, page_size, ppseq, window)
    q0 = np.arange(0, max(int(qw), 1), bq, dtype=np.int64)[None, :]
    _, blocks = _walk(np.asarray(kv_lens, np.int64)[:, None],
                      np.asarray(q_lens, np.int64)[:, None], q0, bq,
                      page_size, group, window, xp=np)
    return int(blocks.sum())


def _tile_slots(lanes: int, rows: int, qw: int, bq: int) -> int:
    """Grid steps of a launch over ``rows`` packed rows: every tile of
    ``bq`` rows of one sequence that ``lanes`` sequences of at most
    ``qw`` rows each can make of them — ``sum(ceil(q_lens / bq)) <=
    rows // bq + lanes`` — and never more than a tile a ``bq`` rows of
    every lane's ``qw``.  Static: from shapes alone."""
    return min(lanes * -(-qw // bq), -(-rows // bq) + lanes)


def launch_tiles(q_lens, rows: int, qw: int, nh: int, hd: int, itemsize: int,
                 page_size: int, ppseq: int, window=None):
    """``(live tiles, grid steps)`` of one launch over ``rows`` packed
    rows, by the kernel's own ``_tiling``, in numpy on the host: what
    the serving engine's ``attn_tiles`` and ``attn_tile_slots`` count a
    layer.  Their ratio is how much of the grid works."""
    bq, _ = _tiling(qw, nh, hd, itemsize, page_size, ppseq, window)
    q_lens = np.asarray(q_lens, np.int64)
    return (int((-(-q_lens // bq)).sum()),
            _tile_slots(len(q_lens), int(rows), int(qw), bq))


def _ragged_kernel(kv_lens_ref, q_lens_ref, tables_ref, tiles_ref, q_ref,
                   k_hbm, v_hbm,
                   *rest, n_kv: int, n_rep: int, block_q: int,
                   page_size: int, group: int, scale: float, window,
                   has_sink: bool, precision=None):
    # the pools stay in HBM; a turn of the walk holds one block of
    # ``group`` pages in one of the two slots of k_buf / v_buf
    if has_sink:
        sink_ref, *rest = rest
    o_ref, acc_ref, m_ref, d_ref, k_buf, v_buf, sems = rest
    keys = group * page_size
    t = pl.program_id(0)
    nh = n_kv * n_rep
    rows = n_rep * block_q               # flat (head, row) of a kv head
    b = tiles_ref[0, t]                  # the tile's sequence
    q0 = tiles_ref[1, t]                 # its first row in the chunk
    kv_len = kv_lens_ref[b]
    q_len = q_lens_ref[b]
    # the walk ends at the tile's last visible key and is empty for a
    # slot past the step's live tiles (its q0 lies past every q_len) —
    # so copies and compute scale with the sequence's OWN lengths, and
    # the grid with the step's own rows
    first, n_blocks = _walk(kv_len, q_len, q0, block_q, page_size, group,
                            window)

    def start(i, slot):
        """Start block ``i``'s copies into ``slot``: one a page a pool,
        every kv head at once.  An entry past the context names whatever
        page the table holds there; its keys are masked."""
        def page_copies(j, carry):
            entry = first + i * jnp.int32(group) + j
            if window is not None:       # the table is a ring
                entry = entry % jnp.int32(tables_ref.shape[1])
            page = tables_ref[b, entry]
            at = pl.ds(pl.multiple_of(j * jnp.int32(page_size), page_size),
                       page_size)
            pltpu.make_async_copy(k_hbm.at[:, page], k_buf.at[slot, :, at],
                                  sems.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[:, page], v_buf.at[slot, :, at],
                                  sems.at[1, slot]).start()
            return carry
        jax.lax.fori_loop(jnp.int32(0), jnp.int32(group), page_copies, None)

    def wait(slot):
        # a copy signals its semaphore by its bytes: one wait a pool for
        # a whole slot's worth is the wait for every page of the block
        for pool, buf in enumerate((k_buf, v_buf)):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sems.at[pool, slot]).wait()

    def turn(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(i + 1, 1 - slot)

        wait(slot)
        # [rows, keys] index planes, the same for every kv head: query
        # row r of head h sits at flat row h*block_q + r; its absolute
        # position is kv_len - q_len + q0 + r
        qi = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 0) % jnp.int32(block_q)
        kvpos = (first + i * jnp.int32(group)) * jnp.int32(page_size) \
            + jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
        qpos = kv_len - q_len + qi
        mask = (kvpos <= qpos) & (kvpos < kv_len)
        if window is not None:
            mask = mask & (kvpos > qpos - jnp.int32(window))
        # every kv head's logits before any statistic is written, every
        # store after the last load: the heads' chains of product, row
        # maximum, exponential and product then overlap instead of
        # queueing behind each other's stores (a decode turn of eight
        # heads took 2.2 times as long head by head on a v5e)
        m_prev = m_ref[...]
        logits = []
        for g in range(n_kv):                            # static GQA loop
            # the wrapper hands q heads-major with block_q a multiple of
            # the 8-sublane tile, so this collapse is layout-trivial
            qg = q_ref[0, g * n_rep:(g + 1) * n_rep] \
                .astype(jnp.float32).reshape(rows, -1)
            s = jax.lax.dot_general(
                qg, k_buf[slot, g].astype(jnp.float32),  # [keys, hd]
                (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            logits.append(jnp.where(mask, s, jnp.float32(-1e30)))
        m_new = jnp.maximum(m_prev, jnp.concatenate(
            [jnp.max(s, axis=-1, keepdims=True) for s in logits], axis=0))
        alpha = jnp.exp(m_prev - m_new)
        # masked probabilities: a fully-masked block must contribute 0,
        # not exp(-1e30 - (-1e30)) == 1
        probs = [jnp.where(mask,
                           jnp.exp(s - m_new[g * rows:(g + 1) * rows]),
                           jnp.float32(0.0))
                 for g, s in enumerate(logits)]
        d_ref[...] = d_ref[...] * alpha + jnp.concatenate(
            [jnp.sum(p, axis=-1, keepdims=True) for p in probs], axis=0)
        acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(
            [jax.lax.dot_general(
                p, v_buf[slot, g].astype(jnp.float32),   # [keys, hdv]
                (((1,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)
             for g, p in enumerate(probs)], axis=0)
        m_ref[...] = m_new
        return carry

    @pl.when(n_blocks == 0)
    def _empty_slot():
        # no copy, no turn: a zero-context row is exactly zero
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live_tile():
        start(jnp.int32(0), 0)
        if has_sink:
            # the sink is a key with no value: the running max starts at
            # its logit and the denominator at exp(0)
            m_ref[...] = sink_ref[...]
            d_ref[...] = jnp.ones_like(d_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, jnp.float32(-1e30))
            d_ref[...] = jnp.zeros_like(d_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(jnp.int32(0), n_blocks, turn, None)
        d = d_ref[...]
        out = jnp.where(d > jnp.float32(0.0), acc_ref[...] / d,
                        jnp.float32(0.0))
        o_ref[0] = out.reshape(nh, block_q, -1).astype(o_ref.dtype)


def row_lanes(offs, n_rows: int):
    """``(lane, at)`` of each of ``n_rows`` packed rows: the sequence
    that owns it — the last one that starts at or before it; an empty
    sequence starts where the next one does and owns no row — and its
    index in that sequence's chunk, which is ``>= q_lens[lane]`` for a
    row that carries no token."""
    row = jnp.arange(n_rows, dtype=jnp.int32)
    lane = jnp.sum(row[:, None] >= offs[None, :], axis=1,
                   dtype=jnp.int32) - jnp.int32(1)
    return lane, row - offs[lane]


def _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens, page_tables,
                   scale, window=None, sinks=None, precision=None):
    """The kernel over a ``[B, Q]`` step: the packed launch with
    sequence ``b``'s rows at ``offs[b] = b * Q``."""
    b, qw, nh, hd = q.shape
    out = _ragged_pallas_rows(
        q.reshape(b * qw, nh, hd), k_pages, v_pages, kv_lens, q_lens,
        jnp.arange(b, dtype=jnp.int32) * jnp.int32(qw), page_tables, qw,
        scale, window, sinks, precision)
    return out.reshape(b, qw, nh, -1)


def _ragged_pallas_rows(q, k_pages, v_pages, kv_lens, q_lens, offs,
                        page_tables, q_width, scale, window=None,
                        sinks=None, precision=None):
    """The kernel's launch, as a jitted function of its own: a step
    calls it once a layer, and the layers of one geometry then share
    one trace and one lowering to Mosaic, a program at a time, cached
    or not."""
    return _ragged_call(q, k_pages, v_pages, kv_lens, q_lens, offs,
                        page_tables, sinks, q_width=int(q_width),
                        scale=float(scale),
                        window=None if window is None else int(window),
                        precision=precision, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("q_width", "scale", "window",
                                             "precision", "interpret"))
def _ragged_call(q, k_pages, v_pages, kv_lens, q_lens, offs, page_tables,
                 sinks, *, q_width, scale, window, precision, interpret):
    i32 = jnp.int32
    hdv = v_pages.shape[-1]
    # a copy out of a pool moves whole 128-lane tiles (Mosaic refuses a
    # slice 96 wide).  The serving step's pools are whole tiles wide
    # already (generation._pool_width), so this is no operation there;
    # a direct caller's pools of another width are padded for the call,
    # a copy of each pool a launch.  The zeros add nothing to q.k
    q, k_pages, v_pages = (
        a if a.shape[-1] % 128 == 0 else jnp.pad(
            a, ((0, 0),) * (a.ndim - 1) + ((0, -a.shape[-1] % 128),))
        for a in (q, k_pages, v_pages))
    n_rows, nh, hd = q.shape
    nkv, _, ps, _ = k_pages.shape
    hdp = v_pages.shape[-1]              # the values' width as copied
    b, ppseq = page_tables.shape
    bq, group = _tiling(q_width, nh, hd, q.dtype.itemsize, ps, ppseq, window)
    n_slots = _tile_slots(b, n_rows, q_width, bq)
    kv_lens, q_lens, offs = (a.astype(i32) for a in (kv_lens, q_lens, offs))
    # the step's live tiles, a lane's one behind the other: slot i is
    # tile (i - first[lane]) of the lane whose tiles it falls among, and
    # a slot past them all starts at a row no chunk has (an empty slot:
    # no copy, zeros out) and names the q block held already
    tiles = -(-q_lens // i32(bq))
    ends = jnp.cumsum(tiles, dtype=i32)
    first = ends - tiles
    slot = jnp.arange(n_slots, dtype=i32)
    lane = jnp.minimum(jnp.sum(slot[:, None] >= ends[None, :], axis=1,
                               dtype=i32), i32(b - 1))
    live = slot < ends[-1]
    q0 = jnp.where(live, (slot - first[lane]) * i32(bq),
                   i32(-(-q_width // bq) * bq))
    held = jnp.minimum(slot, jnp.maximum(ends[-1] - 1, 0))
    # rows into tiles, heads-major a tile [T, nh, bq, hd]: the kernel
    # collapses (n_rep, block_q) into flat rows without an in-kernel
    # transpose.  A tile's rows past its chunk hold some other row,
    # which the kernel never reads into a valid result
    idx = jnp.minimum((offs[lane] + q0)[:, None]
                      + jnp.arange(bq, dtype=i32)[None, :], i32(n_rows - 1))
    qt = jnp.swapaxes(q[idx], 1, 2)
    if window is None and ppseq % group:
        # whole blocks: the added entries lie past every context
        page_tables = jnp.pad(
            page_tables, ((0, 0), (0, group - ppseq % group)), mode="edge")

    in_specs = [pl.BlockSpec((1, nh, bq, hd),
                             lambda t, kl, ql, tb, tl: (tl[2, t], 0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qt, k_pages, v_pages]
    if sinks is not None:
        # one logit per flat (head, row) of the tile
        in_specs.append(pl.BlockSpec(
            (nh * bq, 1), lambda t, kl, ql, tb, tl: (0, 0)))
        operands.append(jnp.repeat(sinks.astype(jnp.float32), bq)[:, None])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nh, bq, hdp),
                               lambda t, kl, ql, tb, tl: (t, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh * bq, hdp), jnp.float32),  # acc
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # running max
            pltpu.VMEM((nh * bq, 1), jnp.float32),    # denominator
            pltpu.VMEM((2, nkv, group * ps, hd), k_pages.dtype),
            pltpu.VMEM((2, nkv, group * ps, hdp), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (k | v, slot)
        ],
    )
    name = "ragged_paged_attn" if window is None \
        else "ragged_paged_attn_window"
    with jax.enable_x64(False), jax.named_scope(name):
        out = pl.pallas_call(
            functools.partial(_ragged_kernel, n_kv=nkv,
                              n_rep=nh // nkv, block_q=bq,
                              page_size=ps, group=group, scale=scale,
                              window=window,
                              has_sink=sinks is not None,
                              precision=precision),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_slots, nh, bq, hdp), q.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(kv_lens, q_lens, page_tables.astype(i32),
          jnp.stack([lane, q0, held]), *operands)
    # tiles back into rows: row r is row (at % bq) of its lane's tile
    # at // bq, and a row that carries no token is zero
    row_lane, at = row_lanes(offs, n_rows)
    src = jnp.minimum(first[row_lane] + at // i32(bq), i32(n_slots - 1)) \
        * i32(bq) + at % i32(bq)
    rows = jnp.swapaxes(out, 1, 2).reshape(n_slots * bq, nh, hdp)[
        src, :, :hdv]
    return jnp.where((at < q_lens[row_lane])[:, None, None], rows,
                     jnp.zeros((), rows.dtype))


def _kernel_takes(q, k_pages, v_pages) -> bool:
    return available() and q.shape[-2] % k_pages.shape[0] == 0 \
        and q.shape[-1] % 8 == 0 and v_pages.shape[-1] % 8 == 0


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
    available (TPU, or CPU interpret mode) — the packed launch of
    :func:`ragged_paged_attention_rows` with sequence ``b``'s rows at
    ``b * Q`` — else the jnp reference; both produce the eager sdpa
    numerics on the valid rows (``i < q_lens[b]``)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _kernel_takes(q, k_pages, v_pages):
        return _ragged_pallas(q, k_pages, v_pages, kv_lens, q_lens,
                              page_tables, scale, window, sinks,
                              precision)
    return ragged_paged_attention_ref(q, k_pages, v_pages, kv_lens,
                                      q_lens, page_tables, scale, window,
                                      sinks)


@jax.named_scope("attn_launch")
def ragged_paged_attention_rows(q, k_pages, v_pages, kv_lens, q_lens, offs,
                                page_tables, q_width: int, scale=None,
                                window=None, sinks=None, precision=None):
    """:func:`ragged_paged_attention` over a step's PACKED rows, as the
    serving step holds them: ``q [rows, nh, hd]`` in which sequence
    ``b`` owns rows ``offs[b] .. offs[b] + q_lens[b] - 1`` (``offs``
    nondecreasing, ``q_lens <= q_width``, static) → ``[rows, nh, hdv]``.
    The kernel's grid is the step's live tiles, so nothing here is
    ``B * q_width`` rows tall; only the jnp reference, the route where
    the kernel is not available, lays the rows out as ``[B, Q]``.
    All of it — tile list, gathers, re-lays, the kernel under its own
    scope — runs under ``attn_launch`` in a device trace (the jitted
    launch's operations carry their caller's path)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _kernel_takes(q, k_pages, v_pages):
        return _ragged_pallas_rows(q, k_pages, v_pages, kv_lens, q_lens,
                                   offs, page_tables, q_width, scale,
                                   window, sinks, precision)
    n_rows = q.shape[0]
    offs = offs.astype(jnp.int32)
    lanes = jnp.minimum(
        offs[:, None] + jnp.arange(q_width, dtype=jnp.int32)[None, :],
        jnp.int32(n_rows - 1))
    out = ragged_paged_attention_ref(q[lanes], k_pages, v_pages, kv_lens,
                                     q_lens, page_tables, scale, window,
                                     sinks)
    lane, at = row_lanes(offs, n_rows)
    return jnp.where(
        (at < q_lens.astype(jnp.int32)[lane])[:, None, None],
        out[lane, jnp.minimum(at, jnp.int32(q_width - 1))],
        jnp.zeros((), out.dtype))
