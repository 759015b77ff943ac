"""Latent attention over the keys an index picks, for serving (DeepSeek
sparse attention over a multi-head-latent cache, as GLM-5's
``glm_moe_dsa`` layers use it).

A token leaves two rows in a layer's cache, both without an axis of
heads: its **latent row** ``[c, k_r]`` (the normed latent and the one
rotated key every head shares, padded to the pool's width) in
``latent_pool [1, P, ps, W]`` and its **index key** in ``index_pool [1,
P, ps, D]``.  A query row ``t`` at position ``p_t`` of its sequence

1. scores every key ``s <= p_t`` of its own sequence,
   ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``, in float32 at
   matmul precision "highest" (a selection is discontinuous);
2. keeps the ``top_k`` keys of the largest scores — exactly: no page or
   block stands for its keys, nothing is approximated; while ``p_t + 1
   <= top_k`` every visible key is kept;
3. attends the kept keys in the latent (the absorbed form): a head's
   unrotated query is carried into the latent by its ``w_uk`` and set
   beside its rotated part, ``q[t, h] = [q_nope w_uk[h], q_r]``; logits
   ``softmax_s(q[t, h] . row_s * scale)`` over the kept rows; the
   weighted sum of their latents leaves through the head's ``w_uv``.  No
   head's keys or values are ever formed, and a step's queries are
   carried over a few rows at a time — the lanes' own, or a block's —
   so that nothing of ``[rows, heads, latent]`` is whole in memory
   either.

Both pools are read through the sequences' page tables.  Two forms, by
what a sequence feeds the step (``models.generation._StepRows``):

* **one row** (a decoding lane; :func:`_attend_lanes`): the lane's index
  keys are gathered through its table, ``jax.lax.top_k`` of the masked
  scores names the kept positions, and only THOSE rows of the latent
  pool are gathered and attended — ``top_k`` rows a lane whatever the
  length of its cache;
* **a chunk** (a prefilling sequence; :func:`_attend_blocks`): the
  chunk is cut into blocks of ``_BLOCK_Q`` rows of one sequence.  A
  block scores its sequence's keys ``_BLOCK_K`` at a time up to the
  block's last position, finds each row's ``top_k``-th largest score
  (:func:`kth_largest`: a radix select over the scores' bits, 32
  counting passes, exact), and attends the sequence's latent rows
  ``_BLOCK_K`` at a time under the mask ``score >= threshold`` with a
  running softmax.  No row's keys are gathered: at contexts up to a few
  ``top_k`` the masked products cost less than a gather of ``top_k``
  rows a query row would (a third to a half on a v5e at 4k-8k keys,
  PERF.md section 6, PR 33), and a block whose rows all see at most
  ``top_k`` keys scores and selects nothing.  Nothing of ``[rows, heads,
  kv_len]`` or ``[rows, top_k, W]`` is ever whole in memory.

A layer with no index attends every visible key: all its sequences take
the second form, with blocks as narrow as the step.

Scopes (``jax.named_scope``), for the device trace: ``index_select``
with ``index_score`` and ``index_topk`` under it, and
``sparse_attention``.  Index constants are pinned int32
(``jax_enable_x64`` is on).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .gated_delta import chunk_blocks

__all__ = ["attend_selected", "index_scores", "kth_largest",
           "ordered_bits"]

_HIGHEST = jax.lax.Precision.HIGHEST
i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32

# query rows a block and keys a turn of the chunked form: a block's
# logits are [_BLOCK_Q, heads, _BLOCK_K] (33.5 MB at 64 heads)
_BLOCK_Q = 128
_BLOCK_K = 1024
_MASKED = -1e30


def index_scores(q_i, w_i, k_i):
    """``I [..., R, S] = sum_h w_i[..., R, h] relu(q_i[..., R, h, :] .
    k_i[..., S, :])`` in float32 at "highest"."""
    dots = jnp.einsum("...rhd,...sd->...rhs", q_i.astype(f32),
                      k_i.astype(f32), precision=_HIGHEST)
    return jnp.sum(w_i.astype(f32)[..., None] * jax.nn.relu(dots), axis=-2)


def ordered_bits(x):
    """float32 -> uint32 that orders as the floats do (a larger float is
    a larger integer; every float maps above 0)."""
    bits = jax.lax.bitcast_convert_type(x.astype(f32), u32)
    return jnp.where(bits >> u32(31) == u32(1), ~bits,
                     bits | u32(0x80000000))


def kth_largest(keys, k: int):
    """Each row's ``k``-th largest of ``keys u32[R, S]`` (0 where a row
    has fewer than ``k`` entries above 0), by a radix select: from the
    highest bit down, a bit is kept where at least ``k`` entries still
    reach the value so far.  32 passes of a comparison and a count,
    exact whatever ``k``."""

    def one_bit(i, prefix):
        cand = prefix | (u32(1) << (u32(31) - i.astype(u32)))
        reach = jnp.sum(keys >= cand[:, None], axis=1, dtype=i32)
        return jnp.where(reach >= i32(k), cand, prefix)

    return jax.lax.fori_loop(i32(0), i32(32), one_bit,
                             jnp.zeros(keys.shape[:1], u32))


def _attend_lanes(queries, heads, index, latent_pool, tables, kv_lens, offs,
                  q_lens, scale: float):
    """The one-row form: ``o [B, heads, value]`` for the first row of
    every sequence (anything for a sequence that feeds no single
    row)."""
    q_i, w_i, index_pool, top_k = index
    absorb, read_out, _ = heads
    n = queries[0].shape[0]
    b, ppseq = tables.shape
    n_pages, ps, width = latent_pool.shape[1:]
    length = ppseq * ps
    k = min(int(top_k), length)
    row = jnp.clip(offs, 0, n - 1)
    last = (kv_lens.astype(i32) - i32(1))[:, None]        # the row's position
    at = jnp.arange(length, dtype=i32)[None, :]
    with jax.named_scope("index_select"):
        with jax.named_scope("index_score"):
            keys = index_pool[0][tables].reshape(b, length, -1)
            score = index_scores(q_i[row][:, None], w_i[row][:, None],
                                 keys)[:, 0]
            score = jnp.where(at <= last, score, -jnp.inf)
        with jax.named_scope("index_topk"):
            # a step none of whose decoding lanes sees more than top_k
            # keys chooses nothing: the first k positions are every key
            choose = jnp.any((q_lens == 1) & (kv_lens > k))
            picked = jax.lax.cond(
                choose, lambda s: jax.lax.top_k(s, k)[1].astype(i32),
                lambda s: jnp.broadcast_to(at[:, :k], (b, k)), score)
    with jax.named_scope("sparse_attention"):
        real = picked <= last
        flat = jnp.take_along_axis(tables.astype(i32), picked // i32(ps),
                                   axis=1) * i32(ps) + picked % i32(ps)
        chosen = latent_pool.reshape(n_pages * ps, width)[flat]  # [B,k,W]
        # the lanes' few query rows are widened to the pool's rows; the
        # gathered rows are read as they lie
        q = absorb(*(a[row] for a in queries))
        q = jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))
        logits = jnp.einsum("bhw,bsw->bhs", q, chosen) * scale
        logits = jnp.where(real[:, None, :], logits, _MASKED)
        return read_out(jnp.einsum(
            "bhs,bsw->bhw", jax.nn.softmax(logits, axis=-1), chosen))


def _attend_blocks(out, queries, heads, index, latent_pool, tables, pos,
                   offs, q_lens, pick, block_q: int, scale: float):
    """The chunked form: ``out [n, heads, value]`` with the rows of every
    sequence in ``pick bool[B]`` overwritten by their attention (the
    other rows as they were)."""
    absorb, read_out, rank = heads
    n, nh = out.shape[:2]
    b, ppseq = tables.shape
    ps, width = latent_pool.shape[2:]
    c = int(block_q)
    pages_turn = max(min(_BLOCK_K // ps, ppseq), 1)
    kb = pages_turn * ps                                  # keys a turn
    pad_pages = -ppseq % pages_turn
    tables = jnp.pad(tables.astype(i32), ((0, 0), (0, pad_pages)))
    length = (ppseq + pad_pages) * ps
    # blocks of c rows, each of one sequence, as gated_delta_chunks
    # cuts them
    nb = chunk_blocks(n, b, c)
    blocks = jnp.where(pick, (q_lens + i32(c - 1)) // i32(c), i32(0))
    first = jnp.cumsum(blocks, dtype=i32) - blocks
    j = jnp.arange(nb, dtype=i32)
    of = jnp.clip(jnp.sum(j[:, None] >= first[None, :], axis=1, dtype=i32)
                  - i32(1), 0, b - 1)
    inside = (j - first[of]) * i32(c)              # the block's first row
    start = offs[of] + inside
    left = q_lens[of] - inside
    pos = pos.astype(i32)
    if index is not None:
        q_i, w_i, index_pool, top_k = index
    at = jnp.arange(kb, dtype=i32)[None, :]
    rows_c = jnp.arange(c, dtype=i32)

    def one_block(i, out):
        s0 = start[i]
        live = rows_c < left[i]
        # the block's rows; past the step's last row some row twice,
        # which is not live
        rows_i = jnp.minimum(s0 + rows_c, i32(n - 1))
        take = lambda a: a[rows_i]
        p = jnp.where(live, take(pos), i32(0))[:, None]          # [c, 1]
        table = tables[of[i]]
        turns = jnp.max(p) // i32(kb) + i32(1)
        pages = lambda t: jax.lax.dynamic_slice_in_dim(
            table, t * i32(pages_turn), pages_turn)
        keys = thr = None
        if index is not None:
            qb, wb = take(q_i), take(w_i)

            def score_turn(t, keys):
                ki = index_pool[0][pages(t)].reshape(kb, -1)
                seen = t * i32(kb) + at <= p
                return jax.lax.dynamic_update_slice_in_dim(
                    keys, jnp.where(seen, ordered_bits(
                        index_scores(qb, wb, ki)), u32(0)),
                    t * i32(kb), axis=1)

            def choose(keys):
                with jax.named_scope("index_score"):
                    keys = jax.lax.fori_loop(i32(0), turns, score_turn, keys)
                with jax.named_scope("index_topk"):
                    return keys, kth_largest(keys, int(top_k))

            # a block whose rows all see at most top_k keys keeps them
            # all and scores nothing (every key then reaches a threshold
            # of 0)
            with jax.named_scope("index_select"):
                keys, thr = jax.lax.cond(
                    jnp.max(p) >= i32(top_k), choose,
                    lambda keys: (keys, jnp.zeros((c,), u32)),
                    jnp.zeros((c, length), u32))
        qc = absorb(*(take(a) for a in queries))
        wide = qc.shape[-1]

        def attend_turn(t, carry):
            m, l, acc = carry
            rows_t = latent_pool[0][pages(t)].reshape(kb, width)
            seen = t * i32(kb) + at <= p                         # [c, kb]
            if index is not None:
                seen &= jax.lax.dynamic_slice_in_dim(
                    keys, t * i32(kb), kb, axis=1) >= thr[:, None]
            seen = seen[:, None, :]
            logits = jnp.where(
                seen, jnp.einsum("rhw,sw->rhs", qc, rows_t[:, :wide])
                * scale, _MASKED)
            m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
            w = jnp.where(seen, jnp.exp(logits - m2[..., None]), 0.0)
            fade = jnp.exp(m - m2)
            return (m2, l * fade + jnp.sum(w, axis=-1),
                    acc * fade[..., None]
                    + jnp.einsum("rhs,sc->rhc", w, rows_t[:, :rank]))

        with jax.named_scope("sparse_attention"):
            _, l, acc = jax.lax.fori_loop(
                i32(0), turns, attend_turn,
                (jnp.full((c, nh), _MASKED, f32), jnp.zeros((c, nh), f32),
                 jnp.zeros((c, nh, rank), f32)))
            o = read_out(acc / jnp.maximum(l, 1e-30)[..., None]) \
                .astype(out.dtype)
        # written as a slice of c rows that ends inside ``out``: the
        # block's live rows at their own places, every other row of the
        # slice as it was
        first_row = jnp.minimum(s0, i32(n - c))
        at_row = first_row + rows_c - s0              # index in the block
        mine = (at_row >= 0) & (at_row < left[i])
        old = jax.lax.dynamic_slice_in_dim(out, first_row, c, axis=0)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(mine[:, None, None],
                           o[jnp.clip(at_row, 0, c - 1)], old),
            first_row, axis=0)

    return jax.lax.fori_loop(i32(0), jnp.sum(blocks, dtype=i32), one_block,
                             out)


def attend_selected(q_nope, q_rot, w_uk, w_uv, latent_pool, index, tables,
                    kv_lens, pos, offs, q_lens, lane, q_width: int,
                    scale: float):
    """``o [n, heads, value]``: every packed row's attention over the
    kept keys of its own sequence.

    ``q_nope [n, heads, nope]`` and ``q_rot [n, heads, rope]`` a head's
    query, its second part rotated; ``w_uk [heads, nope, rank]`` and
    ``w_uv [heads, rank, value]`` the heads' two read-outs of the
    latent; ``latent_pool [1, P, ps, W]`` whose rows hold the latent
    (``rank``), the shared rotated key (``rope``) and padding, with this
    step's rows already written; ``index`` None or ``(q_i [n, ih, D],
    w_i [n, ih], index_pool [1, P, ps, D], top_k)``; ``tables [B,
    pages]`` each sequence's page ids; ``kv_lens [B]`` lengths behind
    the step's rows; ``pos [n]``; sequence ``b`` owns rows ``offs[b] ..
    offs[b] + q_lens[b] - 1`` and ``lane [n]`` is each row's sequence;
    ``q_width`` the step's static width (1: no sequence feeds a
    chunk)."""
    offs, q_lens = offs.astype(i32), q_lens.astype(i32)
    n, nh = q_nope.shape[:2]
    rank = w_uk.shape[-1]
    heads = (
        lambda qn, qr: jnp.concatenate(
            [jnp.einsum("rhd,hdc->rhc", qn, w_uk), qr], axis=-1),
        lambda o: jnp.einsum("rhc,hcv->rhv",
                             o[..., :rank].astype(q_nope.dtype), w_uv),
        rank)
    queries = (q_nope, q_rot)
    block_q = min(_BLOCK_Q, int(q_width))
    if index is None:
        return _attend_blocks(
            jnp.zeros((n, nh, w_uv.shape[-1]), q_nope.dtype), queries, heads,
            None, latent_pool, tables, pos, offs, q_lens, q_lens > 0,
            block_q, scale)
    o = _attend_lanes(queries, heads, index, latent_pool, tables, kv_lens,
                      offs, q_lens, scale).astype(q_nope.dtype)[lane]
    if int(q_width) > 1:
        o = _attend_blocks(o, queries, heads, index, latent_pool, tables,
                           pos, offs, q_lens, q_lens > 1, block_q, scale)
    return o
