"""Fused Adam/AdamW update — one Pallas kernel per parameter.

ref: paddle/phi/kernels/fusion/ fused_adam / fused_adamw (one CUDA
kernel updating p/m/v in a single pass).  TPU-native: the eager
optimizer step launches one kernel per parameter instead of ~10
elementwise XLA ops (under the jitted TrainStep XLA fuses these anyway —
the win is the eager path and deterministic fusion).

The parameter is flattened and padded to (rows, 128) lanes; lr and the
bias-correction powers arrive as a dynamic (1, 8) scalar row (they
change every step — baking them would recompile), betas/eps/wd are
static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...flags import get_flag
from . import kernel_enabled

_LANES = 128


def available() -> bool:
    return kernel_enabled("use_pallas_adamw")


def _adamw_kernel(s_ref, p_ref, g_ref, m_ref, v_ref,
                  po_ref, mo_ref, vo_ref, *, b1: float, b2: float,
                  eps: float, wd: float):
    lr = s_ref[0, 0]
    b1p = s_ref[0, 1]
    b2p = s_ref[0, 2]
    g = g_ref[...].astype(jnp.float32)
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    m_hat = m / (1.0 - b1p)
    v_hat = v / (1.0 - b2p)
    p = p_ref[...].astype(jnp.float32)
    if wd:
        p = p * (1.0 - lr * wd)
    po_ref[...] = (p - lr * m_hat / (jnp.sqrt(v_hat) + eps)).astype(
        po_ref.dtype)
    mo_ref[...] = m
    vo_ref[...] = v


def fused_adamw_update(pv, gv, m, v, lr, b1p, b2p, b1: float, b2: float,
                       eps: float, wd: float = 0.0, block_rows: int = 256):
    """Returns (new_p, new_m, new_v) — numerically identical to the
    unfused jnp sequence (m/v in fp32)."""
    interpret = bool(get_flag("pallas_interpret"))
    shape, dtype = pv.shape, pv.dtype
    n = pv.size
    rows = -(-n // _LANES)
    pad = rows * _LANES - n

    def flat(x, dt):
        x = x.reshape(-1).astype(dt)
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, _LANES)

    p2 = flat(pv, dtype)
    g2 = flat(gv, jnp.float32)
    m2 = flat(m, jnp.float32)
    v2 = flat(v, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         jnp.asarray(b1p, jnp.float32),
                         jnp.asarray(b2p, jnp.float32),
                         jnp.zeros((), jnp.float32)]).reshape(1, 4)
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    with jax.enable_x64(False), jax.named_scope("adamw"):
        po, mo, vo = pl.pallas_call(
            functools.partial(_adamw_kernel, b1=float(b1), b2=float(b2),
                              eps=float(eps), wd=float(wd)),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 4), lambda i: (0, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
                pl.BlockSpec((br, _LANES), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((rows, _LANES), dtype),
                jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            ],
            interpret=interpret,
        )(scalars, p2, g2, m2, v2)

    def unflat(x, dt):
        return x.reshape(-1)[:n].reshape(shape).astype(dt)

    return (unflat(po, dtype), unflat(mo, jnp.float32),
            unflat(vo, jnp.float32))
