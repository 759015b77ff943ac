"""One held expert's SwiGLU over a narrow step's rows, as one kernel
that reads the expert's weights itself.

``expert_swiglu(x [T, H], wg [H, I], wu [H, I], wd [I, H]) -> [T, H]``
is ``(silu(x wg) * (x wu)) wd`` for every row of ``x``.  The three
matrices stay in HBM: the kernel walks the expert width ``I`` in blocks
of columns and copies a block of ``wg``, of ``wu`` and the matching rows
of ``wd`` into one of two VMEM buffers while the block before it
multiplies,

    acc += (silu(x wg_b) * (x wu_b)) wd_b

so every weight byte is read once, by the kernel, where it runs — a
program that calls it inside a branch reads the weights only where the
branch is taken (``ops/routed_experts.py`` says why XLA's own products
do not) — and ``[T, I]`` never leaves VMEM.

The products are float32 at ``Precision.HIGHEST`` (Mosaic lowers
DEFAULT and HIGHEST only; the configurations state ``high`` for the
step's products, and HIGHEST is not below it).  At a narrow step's few
rows the six passes cost nothing that shows: the time is the weights'
bytes.  A v5e took 99 us an expert of 62.9 MB (Solar-Open2's widths, 8
rows, blocks of 256 columns), 93 us with the copies alone and 96 us
with a two-pass bf16 split written out, which was therefore not kept —
77 us at the HBM's 819 GB/s; as it stands here, 95 us (my chip run,
PR 36).

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

__all__ = ["expert_swiglu", "available"]

# two buffers of a block of each of the three matrices (19 MB at a
# hidden size of 6,144) and Mosaic's own temporaries, of the v5e's 128
# MiB of VMEM (Mosaic's default scope is 16)
_VMEM_LIMIT = 64 << 20
# columns of the expert width a turn.  The first block's copy hides
# behind no product and the last block's products behind no copy, so a
# block is a small share of the matrix: 128 read 95.0 us an expert of
# Solar-Open2's where 256 read 100.4, and 215.1 against 220.2 at
# GLM-5's widths (my chip run, PR 36)
_BLOCK = 128
# rows (of wg, wu) and columns (of wd) multiplied at a time: an operand
# of a product is then at most [512, 128]
_CHUNK = 512


def available() -> bool:
    """The narrow serve step's feed-forward kernels go together: this
    one with ``fused_decode.norm_mlp``."""
    return kernel_enabled("use_pallas_fused_decode")


def _dot(x, w):
    # said, not left to the caller's context: Mosaic lowers no "high"
    return jnp.dot(x, w, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _chunks(n: int):
    step = _CHUNK if n % _CHUNK == 0 else n
    return [(at, step) for at in range(0, n, step)]


def _expert_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, g_buf, u_buf,
                   d_buf, sem, *, block: int, n_blocks: int):
    h = x_ref.shape[1]

    def copies(b, slot):
        cols = pl.ds(pl.multiple_of(b * jnp.int32(block), block), block)
        return (pltpu.make_async_copy(wg_ref.at[:, cols], g_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(wu_ref.at[:, cols], u_buf.at[slot],
                                      sem.at[1, slot]),
                pltpu.make_async_copy(wd_ref.at[cols, :], d_buf.at[slot],
                                      sem.at[2, slot]))

    for c in copies(jnp.int32(0), 0):
        c.start()
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.zeros_like(o_ref)

    def turn(b, carry):
        slot = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next_block():
            for c in copies(b + 1, 1 - slot):
                c.start()

        for c in copies(b, slot):
            c.wait()
        g = u = jnp.zeros((x_ref.shape[0], block), jnp.float32)
        for at, n in _chunks(h):
            g = g + _dot(x[:, at:at + n], g_buf[slot, at:at + n, :])
            u = u + _dot(x[:, at:at + n], u_buf[slot, at:at + n, :])
        a = g * jax.nn.sigmoid(g) * u
        for at, n in _chunks(h):
            o_ref[:, at:at + n] += _dot(a, d_buf[slot, :, at:at + n])
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_blocks), turn, None)


def expert_swiglu(x, w_gate, w_up, w_down):
    """``(silu(x w_gate) * (x w_up)) w_down`` for the rows of ``x [T,
    H]``, float32 sums; the weights are read from HBM by the kernel.
    The launch is a jitted function of its own: the experts of one
    geometry share one trace and one lowering to Mosaic, a program at a
    time."""
    return _expert_call(x, w_gate, w_up, w_down,
                        interpret=bool(get_flag("pallas_interpret")))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _expert_call(x, w_gate, w_up, w_down, *, interpret):
    t, h = x.shape
    i = w_gate.shape[1]
    tm = -(-t // 8) * 8
    # a width that no whole block divides (a test's) is one block
    block = _BLOCK if i % _BLOCK == 0 else i
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    with jax.enable_x64(False), jax.named_scope("expert_swiglu"):
        out = pl.pallas_call(
            functools.partial(_expert_kernel, block=block,
                              n_blocks=i // block),
            in_specs=[whole, in_hbm, in_hbm, in_hbm],
            out_specs=whole,
            out_shape=jax.ShapeDtypeStruct((tm, h), jnp.float32),
            scratch_shapes=[
                pltpu.VMEM((2, h, block), w_gate.dtype),
                pltpu.VMEM((2, h, block), w_up.dtype),
                pltpu.VMEM((2, block, h), w_down.dtype),
                pltpu.SemaphoreType.DMA((3, 2)),      # (matrix, slot)
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(jnp.pad(x, ((0, tm - t), (0, 0))), w_gate, w_up, w_down)
    return out[:t].astype(x.dtype)
