"""Pallas fused RMSNorm (ref: paddle/phi/kernels/fusion/ fused_rms_norm
+ incubate/nn/functional/fused_rms_norm.py).

One VMEM-resident pass per row block: x is read once, normalized and
scaled against the MXU-friendly (…, H) layout; the saved inv-rms drives
a hand-written backward (dx in Pallas; dw/db are row reductions that
XLA already does optimally).  ``interpret=True`` runs the same kernels
on CPU for tests (SURVEY.md §4 fake-device strategy).

Grid/blocks: rows are processed in blocks of ``block_n`` with the FULL
hidden dim resident (H == array dim satisfies Mosaic's lane rule; rows
pad via the grid's clamped tail block).  Kernels trace under
enable_x64(False) — see flash_attention.py for why.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_enabled

DEFAULT_BLOCK_N = 256


def available() -> bool:
    return kernel_enabled("use_pallas_rms_norm")


def _fwd_kernel(x_ref, w_ref, o_ref, r_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                      + eps)
    o_ref[...] = (x * r * w[None, :]).astype(o_ref.dtype)
    r_ref[...] = r


def _bwd_kernel(x_ref, w_ref, r_ref, g_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    r = r_ref[...]
    g = g_ref[...].astype(jnp.float32)
    wg = g * w[None, :]
    # dx = r*w*g - r^3 * x * mean(x*w*g)
    s = jnp.mean(x * wg, axis=-1, keepdims=True)
    dx_ref[...] = (r * wg - (r ** 3) * x * s).astype(dx_ref.dtype)


def _fwd(x2d, w, eps: float, block_n: int, interpret: bool):
    n, h = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False):
        out, r = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x2d.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
        )(x2d, w)
    return out, r


def _bwd_dx(x2d, w, r, g2d, block_n: int, interpret: bool):
    n, h = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False):
        return pl.pallas_call(
            _bwd_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
            interpret=interpret,
        )(x2d, w, r, g2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def rms_norm_pallas(x, w, eps: float = 1e-6,
                    block_n: int = DEFAULT_BLOCK_N,
                    interpret: bool = False):
    """y = x * rsqrt(mean(x^2, -1) + eps) * w over [..., H] tensors."""
    out, _ = _rms_fwd(x, w, eps, block_n, interpret)
    return out


def _rms_fwd(x, w, eps, block_n, interpret):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    out, r = _fwd(x2d, w, eps, block_n, interpret)
    return out.reshape(shape), (x2d, w, r)


def _rms_bwd(eps, block_n, interpret, res, g):
    x2d, w, r = res
    g2d = g.reshape(x2d.shape)
    dx = _bwd_dx(x2d, w, r, g2d, block_n, interpret)
    # dw: a cross-row reduction — XLA's job, fused with the cast
    xhat = x2d.astype(jnp.float32) * r
    dw = jnp.sum(g2d.astype(jnp.float32) * xhat, axis=0).astype(w.dtype)
    return dx.reshape(g.shape), dw


rms_norm_pallas.defvjp(_rms_fwd, _rms_bwd)


def reference_rms_norm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * r * w.astype(jnp.float32)).astype(x.dtype)
