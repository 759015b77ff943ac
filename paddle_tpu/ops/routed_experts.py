"""Routed experts for serving: one chip's share of an expert layer.

A deployment spreads an expert layer's ``n_routed`` experts over many
chips; each chip holds ``count`` of them, starting at expert ``first``.
Every chip routes every row over ALL ``n_routed`` experts (the router
is replicated) and computes the part of the layer's output that ITS
experts give for the rows routed to them — zero for a row whose picks
all live elsewhere.  The shares of all chips add up to the layer's
output; nothing here stands in for the other chips or their traffic.

Two functions, pure ``jnp`` under trace:

* :func:`sigmoid_topk_route` — ``g = sigmoid(h W_r)`` in float32 at
  matmul precision "highest" (a selection is discontinuous: at the
  MXU's default single bf16 pass near-ties would flip against a float32
  reference), the ``top_k`` experts with the largest ``g + bias`` (the
  bias steers the selection only; ties go to the lower expert id, as
  ``jax.lax.top_k`` orders them), weights ``g_e / sum_selected g``;
* :func:`held_experts_swiglu` — dropless grouped SwiGLU over the real
  rows: the (row, pick) pairs that name a held expert are sorted by
  expert and each held expert's pairs cut into tiles of ``_TILE_ROWS``; a
  held expert that has a row runs a loop over ITS tiles, which gathers
  a tile's rows, multiplies them with the expert's three matrices and
  adds the weighted result back.  No capacity and no dropped row: the
  work follows the routing (an expert nobody picked is a branch not
  taken), and the largest buffer is one tile.

**What a branch keeps the compiler from, and what not.**  A branch an
expert keeps XLA from MULTIPLYING with, and from casting for the matrix
unit, the weights of an expert nobody picked.  It does not keep XLA
from READING them: its memory-space assignment treats a branch's
weights as operands of the ``conditional`` and, where a matrix fits its
budget of VMEM, fetches it there BEFORE the ``conditional``, taken or
not.  Solar-Open2's 21 MB matrices fit: a decode step of 8 rows, which
hits 10 of the 80 held experts, read three quarters of all 80 ahead of
branches it then did not take, 4.6 ms of a 14.7 ms step (PERF.md
section 6, PR 36; ``tools/aot_prefetch_tally.py`` counts such fetches
in a compiled step).  So in a **narrow step** — the step's (row, pick)
pairs fit one tile, ``T * k <= _TILE_ROWS`` (:func:`narrow_step`): the
decode-only program — a taken branch is one Pallas kernel
(``ops/pallas/expert_swiglu.py``) that is handed the three matrices in
HBM and copies them block by block itself: what is read is what the
routing picked.  It multiplies all ``T`` rows (an expert cannot be given
more, and at so few rows the time is the weights' bytes) and the branch
adds back the rows that picked the expert.  A wider step keeps XLA's
products: there every held expert is hit, the fetch ahead of the branch
is wanted work that overlaps the expert before, and the products are
the matrix unit's time, at three passes where the kernel has six.

Index constants are pinned int32 (``jax_enable_x64`` is on).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .pallas import expert_swiglu

__all__ = ["sigmoid_topk_route", "held_experts_swiglu", "narrow_step"]

_TILE_ROWS = 256


def narrow_step(rows: int, top_k: int) -> bool:
    """Whether :func:`held_experts_swiglu` over a step of ``rows`` rows
    of ``top_k`` picks each runs its taken branches as the kernel that
    reads an expert's weights itself: the pairs fit one tile, and the
    kernel is available (a TPU, or interpret mode).  Shapes alone: the
    serving engine counts its ``expert_kernel_layers`` by the same
    rule."""
    return int(rows) * int(top_k) <= _TILE_ROWS \
        and expert_swiglu.available()


def sigmoid_topk_route(h, w_router, bias, top_k: int):
    """``h [T, H]``, ``w_router [H, n_routed]``, ``bias [n_routed]`` ->
    ``(ids i32[T, top_k], weights f32[T, top_k])``."""
    g = jax.nn.sigmoid(jnp.matmul(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(g + bias.astype(jnp.float32), int(top_k))
    picked = jnp.take_along_axis(g, ids, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return ids.astype(jnp.int32), weights


def held_experts_swiglu(h, ids, weights, valid, w_gate, w_up, w_down,
                        first: int):
    """The held experts' share of ``y = sum_e w_e * SwiGLU_e(h)``.

    ``h [T, H]``; ``ids/weights [T, k]`` from the router; ``valid
    bool[T]`` (padding rows route nowhere); ``w_gate/w_up`` (``C``
    arrays ``[H, I]``) and ``w_down`` (``C`` arrays ``[I, H]``) of the
    ``C`` experts held here, which are experts ``first .. first + C - 1``
    of the layer.  Hand them over as sequences of separate arrays: a
    stacked ``[C, H, I]`` array works, but each branch below is then
    given a slice of it, and XLA casts the whole stack for the MXU
    wherever one expert is hit.

    Returns ``(y [T, H], rows i32[C])``: ``rows[e]`` is the number of
    rows routed to held expert ``e``."""
    t, k = ids.shape
    c = len(w_gate)
    n = t * k
    tm = -(-min(_TILE_ROWS, n) // 8) * 8
    i32 = jnp.int32
    local = ids.astype(i32) - i32(first)
    here = (local >= 0) & (local < c) & valid[:, None]
    key = jnp.where(here, local, i32(c)).reshape(n)
    # pairs sorted by held expert, the pairs of other chips last
    key, order = jax.lax.sort((key, jnp.arange(n, dtype=i32)), num_keys=1)
    rows = jnp.sum(key[None, :] == jnp.arange(c, dtype=i32)[:, None],
                   axis=1, dtype=i32)                     # [C]
    first_pair = jnp.cumsum(rows, dtype=i32) - rows
    tiles = (rows + i32(tm - 1)) // i32(tm)
    # one tile of slack, so that a tile's slice never leaves the arrays
    token = jnp.concatenate([order // i32(k), jnp.zeros((tm,), i32)])
    weight = jnp.concatenate(
        [weights.reshape(n)[order].astype(jnp.float32),
         jnp.zeros((tm,), jnp.float32)])

    def rows_of(e: int):
        """Held expert ``e`` over a narrow step: the kernel multiplies
        every row of the step and reads the three matrices itself,
        here, inside the branch; a row that did not pick ``e`` adds
        nothing, whatever the kernel made of it."""
        wg, wu, wd = w_gate[e], w_up[e], w_down[e]
        mine = here & (local == i32(e))
        w = jnp.sum(jnp.where(mine, weights.astype(jnp.float32),
                              jnp.float32(0.0)), axis=1)

        def add(y):
            out = expert_swiglu.expert_swiglu(h, wg, wu, wd)
            return y + jnp.where(jnp.any(mine, axis=1)[:, None],
                                 out * w[:, None].astype(out.dtype),
                                 jnp.zeros((), out.dtype))

        return add

    def tiles_of(e: int):
        """The loop over held expert ``e``'s tiles (``e`` is static: its
        three matrices are the only weights the branch below is given)."""
        wg, wu, wd = w_gate[e], w_up[e], w_down[e]

        def one_tile(j, y):
            start = first_pair[e] + j * i32(tm)
            live = jnp.arange(tm, dtype=i32) < rows[e] - j * i32(tm)
            idx = jnp.where(
                live, jax.lax.dynamic_slice(token, (start,), (tm,)), i32(0))
            w = jnp.where(
                live, jax.lax.dynamic_slice(weight, (start,), (tm,)),
                jnp.float32(0.0))
            x = h[idx]                                    # [tm, H]
            out = jnp.matmul(jax.nn.silu(jnp.matmul(x, wg))
                             * jnp.matmul(x, wu), wd)
            return y.at[idx].add(out * w[:, None].astype(out.dtype))

        return lambda y: jax.lax.fori_loop(i32(0), tiles[e], one_tile, y)

    branch = rows_of if narrow_step(t, k) else tiles_of
    y = jnp.zeros_like(h)
    with jax.named_scope("expert_matmul"):
        for e in range(c):
            # a branch an expert: what XLA prepares of an expert's
            # weights before its loop (the cast for the MXU) then runs
            # only where the expert has a row.  One loop over all tiles
            # with the expert picked by a dynamic slice of a stacked
            # array had that cast hoisted above the loop and over all C
            # experts: every held expert's weights read and re-written a
            # layer a step, 11 ms of a 26 ms decode step (PERF.md
            # section 6, PR 27).  The branch does not keep XLA from
            # fetching the weights into VMEM ahead of it (the module
            # docstring): in a narrow step the branch is the kernel
            # that reads them itself
            y = jax.lax.cond(rows[e] > 0, branch(e), lambda y: y, y)
    return y, rows
