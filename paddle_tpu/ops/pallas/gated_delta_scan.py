"""The chunked form of the gated delta rule (``ops/gated_delta.py``) as
one kernel that keeps each sequence's state in VMEM across its blocks.

``gated_delta_scan(state, q, k, v, g, beta, blocks, total)`` takes a
step's packed rows (``q, k, g [n, H, dk]``, ``v [n, H, dv]``), each
block's write strengths heads first (``beta [nb, H, C]``, 0 on a row
that is not the block's) and the state of every slot ``[slots, H, dk,
dv]`` float32.  A block is ``C`` rows of one sequence.  ``blocks i32[6,
nb]`` gives each block's state slot, whether it is its sequence's first
and its last block, the packed row it is read from, and the rows ``lo
.. hi - 1`` of those ``C`` that are its own; ``total`` counts the live
blocks.  Both ride as scalar prefetch.  The grid walks a group of
``_HEADS`` heads at a time through the step's blocks in order, and the
pipeline copies a block's ``C`` rows of the group straight from the
packed arrays (a block that would run past the last row is read from
``n - C`` on, its own rows ``lo`` rows in).  A sequence's state is
copied from its slot into VMEM at its first block, carried there
through its blocks and written back once, at its last: the state array
is the output's buffer (``input_output_aliases``), so a slot with no
wide sequence is never touched.  A block past ``total`` fetches nothing
(its index maps stay on the last live block) and writes nothing.  The
output is ``[nb, H, C, dv]``, a block's rows where it read them.

Inside a block, per head, with ``G`` the running sum of ``g`` over the
block's rows (a product with the lower triangle of ones; a row that is
not the block's own has ``g = beta = 0`` and so is a step that changes
nothing)::

    A_kk = (K exp(G - G_m)) (K exp(G_m - G))^T        G_m: the middle row
    A_qk = tril((Q exp(G - G_m)) (K exp(G_m - G))^T)
    T    = (I + B tril(A_kk, -1))^-1                  B = diag(beta)
    U    = T B (V - K exp(G) S_0)
    O    = Q exp(G) S_0 + A_qk U
    S_C  = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U

as the XLA form computes them.  The unit lower triangle is inverted
exactly up to rounding in blocks that double: with ``T_b`` the block
diagonal of the inverses of ``L``'s diagonal blocks of ``b`` rows and
``M_b`` the part of ``L`` below those blocks inside each diagonal block
of ``2 b``, ``T_2 = I - M_1`` and ``T_2b = T_b - T_b M_b T_b`` (a lower
triangle's inverse by halves, ``[[X, 0], [-Y L_21 X, Y]]``), so ``T_C =
L^-1`` after ``log2 C`` levels.  A product with a ``[C, C]`` factor
takes two heads side by side, ``[A | B] [[x_A, 0], [0, x_B]] = [A x_A |
B x_B]``, so that at ``C = 64`` it fills the matrix unit's 128 lanes
(the heads pair up: an odd number of heads keeps the XLA form).  Every
product is float32 at ``Precision.HIGHEST`` (Mosaic lowers DEFAULT and
HIGHEST only).  On one TPU v5e the whole chunked form of a layer at
Solar-Open2's widths (64 heads, dk = dv = 128, a step of 1,032 rows
with chunks of 600 and 400 rows: 17 live blocks of 64) took 1.34 ms
this way and 5.97 ms as the XLA form.

Index constants are pinned int32 (``jax_enable_x64`` is on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...flags import get_flag
from . import kernel_enabled

__all__ = ["gated_delta_scan", "takes"]

# heads a grid step: its blocks of q, k, g and v are 256 KB each at
# Solar-Open2's widths (blocks of 64 rows, dk = dv = 128), its state
# 512 KB; 16 took the same time on a TPU v5e
_HEADS = 8
_HIGHEST = jax.lax.Precision.HIGHEST
i32 = jnp.int32


def takes(heads: int) -> bool:
    """Whether :func:`ops.gated_delta.gated_delta_chunks` over ``heads``
    heads runs as this kernel: the kernel is available (a TPU with no
    mesh axis left to GSPMD, or interpret mode) and the heads pair up.
    The serving engine counts its ``scan_kernel_layers`` by the same
    rule."""
    return kernel_enabled() and heads % 2 == 0


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def _halves(x, c: int):
    """``x [P, C, 2 C]``, a pair of heads' ``[C, C]`` side by side, as
    the block diagonal ``[P, 2 C, 2 C]`` whose product with the pair's
    left factors ``[A | B]`` is ``[A x_A | B x_B]``."""
    lane = jax.lax.broadcasted_iota(i32, x.shape, 2)
    return jnp.concatenate([jnp.where(lane < c, x, 0.0),
                            jnp.where(lane >= c, x, 0.0)], axis=1)


def _stacked(a, b):
    """``a, b [P, C, d]`` as ``[[a, 0], [0, b]]``, ``[P, 2 C, 2 d]``."""
    zero = jnp.zeros_like(a)
    return jnp.concatenate([jnp.concatenate([a, zero], axis=2),
                            jnp.concatenate([zero, b], axis=2)], axis=1)


def _inverse_unit_lower(n, c: int):
    """``(I + n)^-1`` for each of a pair's strictly lower triangular
    ``n`` (``[P, C, 2 C]``, side by side), by blocks that double (module
    docstring): ``T_2 = I - M_1``, then two products a level."""
    r = jax.lax.broadcasted_iota(i32, n.shape, 1)
    col = jax.lax.broadcasted_iota(i32, n.shape, 2) & (c - 1)
    def part(level):
        rb, cb = r >> level, col >> level
        return jnp.where((rb == cb + 1) & ((rb & 1) == 1), n, 0.0)
    t = (r == col).astype(jnp.float32) - part(0)
    level = 1
    while (1 << level) < c:
        x = _dot("pij,pjk->pik", part(level), _halves(t, c))
        t = t - _dot("pij,pjk->pik", t, _halves(x, c))
        level += 1
    return t


def _scan_kernel(blk_ref, total_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                 _state_in, o_ref, state_ref, s_vmem, sem, *, heads: int):
    group, j = pl.program_id(0), pl.program_id(1)
    slot = blk_ref[0, j]
    at = pl.ds(pl.multiple_of(group * heads, heads), heads)
    c, _, dk = q_ref.shape
    dv = v_ref.shape[2]
    half = heads // 2
    left, right = slice(0, half), slice(half, heads)

    @pl.when(j < total_ref[0])
    def _block():
        @pl.when(blk_ref[1, j] == 1)
        def _load():
            copy = pltpu.make_async_copy(state_ref.at[slot, at], s_vmem,
                                         sem.at[0])
            copy.start()
            copy.wait()

        # the block's rows, heads first; a row that is not the block's
        # own (before ``lo``, from ``hi`` on) decays nothing and, with a
        # write strength of 0, writes nothing
        row = jax.lax.broadcasted_iota(i32, (1, c, 1), 1)
        own = (row >= blk_ref[4, j]) & (row < blk_ref[5, j])
        q, k, v, g = (jnp.stack([ref[:, h, :] for h in range(heads)])
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        k, g = jnp.where(own, k, 0.0), jnp.where(own, g, 0.0)
        r = jax.lax.broadcasted_iota(i32, (heads, c, c), 1)
        col = jax.lax.broadcasted_iota(i32, (heads, c, c), 2)
        # beta as a column: the diagonal of a square of it, summed
        beta = jnp.sum(jnp.where(r == col, b_ref[...][:, None, :], 0.0),
                       axis=2, keepdims=True)                # [h, C, 1]
        run = _dot("hts,hsk->htk", (r >= col).astype(jnp.float32), g)
        mid = run[:, c // 2:c // 2 + 1]
        last = run[:, c - 1:c]
        k_down = k * jnp.exp(mid - run)
        k_up, q_up = k * jnp.exp(run - mid), q * jnp.exp(run - mid)
        decay = jnp.exp(run)
        s0 = s_vmem[...]                                     # [h, dk, dv]
        # two heads a product where one of its factors is [C, C]: a pair
        # side by side fills the matrix unit's 128 lanes
        pair = lambda x: jnp.concatenate([x[left], x[right]], axis=2)
        k_down2 = _stacked(k_down[left], k_down[right])      # [P, 2C, 2dk]
        lanes = jax.lax.broadcasted_iota(i32, (half, c, 2 * c), 2)
        rows = jax.lax.broadcasted_iota(i32, (half, c, 2 * c), 1)
        a_kk = _dot("ptl,psl->pts", pair(k_up), k_down2)     # [P, C, 2C]
        a_qk = jnp.where(rows >= (lanes & (c - 1)),
                         _dot("ptl,psl->pts", pair(q_up), k_down2), 0.0)
        beta2 = jnp.where(lanes < c, beta[left], beta[right])
        t = _inverse_unit_lower(
            jnp.where(rows > (lanes & (c - 1)), beta2 * a_kk, 0.0), c)
        # U = T B (V - K exp(G) S_0), then O = Q exp(G) S_0 + A_qk U
        w = beta * (v - _dot("htk,hkv->htv", k * decay, s0))
        u2 = _dot("pts,psv->ptv", t, _stacked(w[left], w[right]))
        u = jnp.concatenate([u2[..., :dv], u2[..., dv:]], axis=0)
        o2 = _dot("pts,psv->ptv", a_qk, _stacked(u[left], u[right]))
        o_ref[...] = _dot("htk,hkv->htv", q * decay, s0) \
            + jnp.concatenate([o2[..., :dv], o2[..., dv:]], axis=0)
        # S_C = diag(exp(G_C)) S_0 + (K exp(G_C - G))^T U
        rk = jax.lax.broadcasted_iota(i32, (heads, dk, dk), 1)
        ck = jax.lax.broadcasted_iota(i32, (heads, dk, dk), 2)
        end = jnp.sum(jnp.where(rk == ck, jnp.exp(last), 0.0), axis=2,
                      keepdims=True)                         # [h, dk, 1]
        s_vmem[...] = end * s0 \
            + _dot("htk,htv->hkv", k * jnp.exp(last - run), u)

        @pl.when(blk_ref[2, j] == 1)
        def _store():
            copy = pltpu.make_async_copy(s_vmem, state_ref.at[slot, at],
                                         sem.at[1])
            copy.start()
            copy.wait()


def gated_delta_scan(state, q, k, v, g, beta, blocks, total):
    """``(o [nb, H, C, dv], state')``: the chunked form over the blocks
    (module docstring), the state updated in place.  Row ``i`` of block
    ``b`` of ``o`` is packed row ``blocks[3, b] + i``; a row that is not
    the block's own, and a block past ``total``, holds anything."""
    return _scan_call(state, q, k, v, g, beta, blocks, total,
                      interpret=bool(get_flag("pallas_interpret")))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_call(state, q, k, v, g, beta, blocks, total, *, interpret):
    nb, nh, c = beta.shape
    dk, dv = q.shape[-1], v.shape[-1]
    heads = _HEADS if nh % _HEADS == 0 else nh

    def live(j, tot):
        # a block past the live ones stays on the last live one
        return jnp.minimum(j, jnp.maximum(tot[0] - 1, 0))

    def rows(d):                          # C packed rows from ``start``
        return pl.BlockSpec(
            (pl.Element(c), pl.Element(heads), pl.Element(d)),
            lambda group, j, blk, tot: (blk[3, live(j, tot)],
                                        group * heads, 0))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nh // heads, nb),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((None, heads, c), lambda group, j, blk, tot:
                               (live(j, tot), group, 0)),
                  in_hbm],
        out_specs=[pl.BlockSpec((None, heads, c, dv),
                                lambda group, j, blk, tot:
                                (live(j, tot), group, 0, 0)),
                   in_hbm],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    with jax.enable_x64(False), jax.named_scope("gated_delta_scan"):
        return pl.pallas_call(
            functools.partial(_scan_kernel, heads=heads),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((nb, nh, c, dv), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(blocks.astype(i32), total.reshape(1).astype(i32), q, k, v, g,
          beta, state)
