"""Gated delta-rule linear attention for serving (Kimi Delta Attention,
arXiv:2510.26692): a fixed-size state a sequence where softmax
attention keeps a cache that grows.

Per head, with keys ``k_t`` of unit length, a log decay ``g_t`` a key
channel (``g <= 0``) and a write strength ``beta_t``::

    S'  = diag(exp(g_t)) S_{t-1}                  S [dk, dv], float32
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

The state of every sequence lives in ONE array ``state [slots, heads,
dk, dv]``, a slot a running sequence, which a serving step takes donated
and hands back: both forms below update it in place and leave every
slot that has no row in the step as it was.

* :func:`gated_delta_step` — one token a sequence (decode rows): the
  recurrence itself, elementwise over the whole array in slot order,
  multiplies and reductions in float32 on the vector unit (no matrix
  product: the step is the read and the write of the state);
* :func:`gated_delta_chunks` — a prefill chunk's rows: the chunked form.
  A chunk is cut into blocks of ``block`` rows, every block of one
  sequence.  Inside a block the recurrence unrolls to

      U~ = T (V - K+ S_0),   T = (I + B tril(K+ K-^T, -1))^-1 B
      O  = Q+ S_0 + tril(Q+ K-^T) U~
      S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U~

  with ``G`` the running sum of ``g`` inside the block, ``K+ = K
  exp(G)``, ``K- = K exp(-G)``, ``B = diag(beta)``.  On a TPU (and in
  interpret mode), for an even number of heads, the blocks run as ONE
  kernel, ``pallas/gated_delta_scan.py``, that reads them from the
  packed rows, walks them in order and keeps a sequence's state in VMEM
  from its first block to its last (``gated_delta_scan.takes``).
  Elsewhere the XLA form runs on the rows gathered into blocks:
  everything that does not hold ``S_0`` (``T V``, ``T K+``, the two
  triangles) for all blocks at once, then a loop over the blocks, as
  many turns as the step has blocks, that carries the state.  The
  products ``K+ K-^T`` are taken about the block's middle row
  (``exp(G - G_mid)``, ``exp(G_mid - G)``) so that neither factor
  leaves float32's range while the log decay summed over HALF a block
  stays above about -80: with blocks of 64 a mean decay of ``exp(g) >=
  0.08`` a step.  Matrix products run at precision "highest".
* :func:`short_conv_rows` — the causal depthwise convolution over the
  last ``K`` positions that feeds q, k and v, over packed rows, with the
  ``K - 1`` rows before a chunk kept a slot in ``tail``.

Rows are a serving step's packed token rows (``models.generation.
_StepRows``): sequence ``b`` owns rows ``offs[b] .. offs[b] + q_lens[b]
- 1`` and state slot ``slot[b]``; ``lane [n]`` and ``at [n]`` give each
row's sequence and its index in the chunk.  A sequence with
``reset[b]`` starts from a zero state and a zero tail whatever its slot
held.  Index constants are pinned int32 (``jax_enable_x64`` is on).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas import gated_delta_scan

__all__ = ["short_conv_rows", "gated_delta_step", "gated_delta_chunks",
           "chunk_blocks"]

_HIGHEST = jax.lax.Precision.HIGHEST
i32 = jnp.int32


def short_conv_rows(x, w, tail, offs, q_lens, slot, reset, at):
    """``y_t = sum_i w[i] x_{t - (K - 1) + i}`` over each sequence's own
    rows and, before its chunk, its slot's ``tail``.

    ``x [n, D]`` packed rows; ``w [K, D]``; ``tail [slots, K - 1, D]``,
    a slot's last ``K - 1`` inputs, oldest first.  Returns ``(y [n, D],
    tail')``; a slot whose sequence has no row keeps its tail."""
    n, k1 = x.shape[0], w.shape[0] - 1
    y = x * w[k1]
    for j in range(1, k1 + 1):                  # x_{t-j}, inside the chunk
        back = jnp.pad(x, ((j, 0), (0, 0)))[:n]
        y = y + jnp.where((at >= j)[:, None], back, 0.0) * w[k1 - j]
    active = q_lens > 0
    old = jnp.where(reset[:, None, None], 0.0,
                    tail[jnp.where(active, slot, i32(0))])   # [B, K-1, D]
    # the first K - 1 rows of a chunk reach back into the tail (a row
    # the chunk does not have adds zero to row 0: no index leaves y)
    fix = jnp.stack([
        sum(w[k1 - j] * old[:, k1 + a - j] for j in range(a + 1, k1 + 1))
        for a in range(k1)], axis=1)                         # [B, K-1, D]
    a = jnp.arange(k1, dtype=i32)[None, :]
    there = a < q_lens[:, None]
    rows = jnp.where(there, offs[:, None] + a, i32(0))
    y = y.at[rows.reshape(-1)].add(
        jnp.where(there[:, :, None], fix, 0.0).reshape(-1, x.shape[1]))
    # the tail after the chunk: its last K - 1 rows, or what is left of
    # the old tail before a chunk shorter than that
    c = q_lens[:, None] - i32(k1) + a                        # [B, K-1]
    new = jnp.where(
        (c >= 0)[:, :, None],
        x[jnp.clip(offs[:, None] + c, 0, n - 1)],
        jnp.take_along_axis(old, jnp.clip(c + k1, 0, k1 - 1)[:, :, None],
                            axis=1))
    # written slot by slot, as the state is: a slot takes its sequence's
    # new tail or keeps its own
    owner, owned = _by_slot(tail.shape[0], slot, active)
    tail = jnp.where(owned[:, None, None], new[owner].astype(tail.dtype),
                     tail)
    return y, tail


def _by_slot(n_slots: int, slot, pick):
    """``(lane_of_slot [slots], has [slots])``: the sequence among
    ``pick [B]`` that owns each slot."""
    owns = (slot[None, :] == jnp.arange(n_slots, dtype=i32)[:, None]) \
        & pick[None, :]                                      # [slots, B]
    return jnp.argmax(owns, axis=1).astype(i32), jnp.any(owns, axis=1)


def gated_delta_step(state, q, k, v, g, beta, offs, q_lens, slot, reset):
    """The recurrence for every sequence that feeds ONE row, in place,
    and a zero state for every sequence that starts (``reset``), whatever
    it feeds.

    ``state [slots, H, dk, dv]``; ``q, k, g [n, H, dk]``; ``v [n, H,
    dv]``; ``beta [n, H]``.  Returns ``(o [slots, H, dv], state')``:
    ``o[s]`` is the output of the row of the sequence in slot ``s``
    (anything where that sequence fed no single row)."""
    n_slots = state.shape[0]
    lane, has = _by_slot(n_slots, slot, q_lens == 1)
    _, cleared = _by_slot(n_slots, slot, reset & (q_lens > 0))
    row = jnp.clip(offs[lane], 0, q.shape[0] - 1)
    q, k, v, g, beta = (a[row] for a in (q, k, v, g, beta))
    s0 = jnp.where(cleared[:, None, None, None], 0.0, state)
    sd = s0 * jnp.exp(g)[..., None]                          # S'
    ks = jnp.sum(k[..., None] * sd, axis=2)                  # S'^T k
    qs = jnp.sum(q[..., None] * sd, axis=2)                  # S'^T q
    u = beta[..., None] * (v - ks)
    o = qs + jnp.sum(q * k, axis=-1, keepdims=True) * u
    new = sd + k[..., None] * u[:, :, None, :]
    return o, jnp.where(has[:, None, None, None], new, s0)


def chunk_blocks(n_rows: int, n_seqs: int, block: int) -> int:
    """Blocks of ``block`` rows that ``n_rows`` packed rows of at most
    ``n_seqs`` sequences can need, each sequence's chunk cut on its
    own."""
    return max((int(n_rows) + int(n_seqs) * (int(block) - 1))
               // int(block), 1)


def gated_delta_chunks(state, q, k, v, g, beta, offs, q_lens, slot,
                       lane, at, block: int):
    """The chunked form for every sequence that feeds MORE than one
    row, in place.  Arguments as :func:`gated_delta_step` (a sequence
    that starts has been cleared by it); ``lane, at [n]`` each row's
    sequence and index in its chunk.  Returns ``(o [n, H, dv],
    state')``: a row of a sequence that feeds one row or none, or past
    its chunk's end, holds anything finite."""
    n, nh, _ = q.shape
    dv = v.shape[-1]
    c = int(block)
    nb = chunk_blocks(n, offs.shape[0], c)
    wide = q_lens > 1
    blocks = jnp.where(wide, (q_lens + i32(c - 1)) // i32(c), i32(0))
    first = jnp.cumsum(blocks, dtype=i32) - blocks           # [B]
    total = jnp.sum(blocks, dtype=i32)
    j = jnp.arange(nb, dtype=i32)
    # a block's sequence: the last one whose blocks start at or before it
    of = jnp.clip(jnp.sum(j[:, None] >= first[None, :], axis=1,
                          dtype=i32) - i32(1), 0, offs.shape[0] - 1)
    # a block's first row, and its rows (at most C; none past the live
    # blocks)
    begin = offs[of] + (j - first[of]) * i32(c)
    count = jnp.where(j < total, jnp.clip(
        q_lens[of] - (j - first[of]) * i32(c), 0, c), i32(0))
    i = jnp.arange(c, dtype=i32)[None, :]
    if gated_delta_scan.takes(nh):
        # the kernel reads a block's C rows from the packed arrays itself,
        # from ``start``: a block that would run past the last row is
        # pulled back to end there, its own rows ``lo`` rows in
        start = jnp.minimum(begin, i32(n - c))
        lo = begin - start
        live = (i >= lo[:, None]) & (i < (lo + count)[:, None])
        bb = jnp.where(live[:, None, :], jnp.swapaxes(
            beta[start[:, None] + i], 1, 2), 0.0)            # [nb, H, C]
        marks = jnp.stack([slot[of], (j == first[of]).astype(i32),
                           (j == first[of] + blocks[of] - 1).astype(i32),
                           start, lo, lo + count])
        out, state = gated_delta_scan.gated_delta_scan(
            state, q, k, v, g, bb, marks, total)
    else:
        lo = jnp.zeros_like(j)
        live = i < count[:, None]
        src = jnp.clip(begin[:, None] + i, 0, n - 1)         # [nb, C]

        def heads_first(a):               # [nb, C, H, d] -> [nb, H, C, d]
            return jnp.swapaxes(a[src], 1, 2)

        okh = live[:, None, :]                               # [nb, 1, C]
        out, state = _scan_blocks(
            state, heads_first(q), heads_first(k), heads_first(v),
            jnp.where(okh[..., None], heads_first(g), 0.0),
            jnp.where(okh, jnp.swapaxes(beta[src], 1, 2), 0.0),
            slot[of], total)
    # back to rows: row ``at`` of sequence ``lane`` sits in that
    # sequence's block ``at // C`` at ``lo + at % C``; a row past its
    # chunk's end can land in a block past the live ones, which the
    # kernel never writes, and reads 0 there as the XLA form's does
    held = jnp.clip(first[lane] + at // i32(c), 0, nb - 1)
    where = jnp.clip(held * i32(c) + lo[held] + at % i32(c), 0, nb * c - 1)
    flat = jnp.swapaxes(out, 1, 2).reshape(nb * c, nh, dv)
    return jnp.where((where < total * i32(c))[:, None, None], flat[where],
                     0.0), state


def _scan_blocks(state, qb, kb, vb, gb, bb, slot_of, total):
    """The XLA form of the chunked scan over blocks heads first (``qb,
    kb, gb [nb, H, C, dk]``, ``vb [nb, H, C, dv]``, ``bb [nb, H, C]``;
    block ``i`` of slot ``slot_of[i]``, the first ``total`` live):
    ``(out [nb, H, C, dv], state')``, what
    ``pallas.gated_delta_scan`` computes as one kernel."""
    nb, nh, c, _ = qb.shape
    dv = vb.shape[-1]
    run = jnp.cumsum(gb, axis=2)                             # G
    mid = run[:, :, c // 2:c // 2 + 1]
    up, down = jnp.exp(run - mid), jnp.exp(mid - run)
    decay = jnp.exp(run)
    k_plus, q_plus = kb * decay, qb * decay
    k_end = kb * jnp.exp(run[:, :, -1:] - run)
    mm = lambda a, b, spec: jnp.einsum(spec, a, b, precision=_HIGHEST)
    a_kk = mm(kb * up, kb * down, "bhtk,bhsk->bhts")
    a_qk = jnp.tril(mm(qb * up, kb * down, "bhtk,bhsk->bhts"))
    # (I + B tril(A_kk, -1)) X = B [V, K+]
    lower = jnp.eye(c, dtype=a_kk.dtype) \
        + bb[..., None] * jnp.tril(a_kk, -1)
    solved = jax.scipy.linalg.solve_triangular(
        lower, bb[..., None] * jnp.concatenate([vb, k_plus], axis=-1),
        lower=True, unit_diagonal=True)
    u_all, w_all = solved[..., :dv], solved[..., dv:]

    def one_block(i, carry):
        st, out = carry
        s0 = st[slot_of[i]]                                  # [H, dk, dv]
        u = u_all[i] - mm(w_all[i], s0, "htk,hkv->htv")
        o = mm(q_plus[i], s0, "htk,hkv->htv") \
            + mm(a_qk[i], u, "hts,hsv->htv")
        s1 = decay[i, :, -1, :, None] * s0 \
            + mm(k_end[i], u, "htk,htv->hkv")
        return (jax.lax.dynamic_update_index_in_dim(st, s1, slot_of[i], 0),
                jax.lax.dynamic_update_index_in_dim(out, o, i, 0))

    state, out = jax.lax.fori_loop(
        i32(0), total, one_block,
        (state, jnp.zeros((nb, nh, c, dv), state.dtype)))
    return out, state
