"""Pallas fused LayerNorm (ref: paddle/phi/kernels/fusion/
fused_layernorm + layer_norm_kernel.cu — the other normalization in the
hot set next to rms_norm; BERT/GPT-2-family blocks call it twice per
layer).

Same shape as the rms_norm kernel: one VMEM-resident pass per row
block with the full hidden dim in-lane, fp32 statistics, saved
(mean, rstd) driving a hand-written backward.  dx is computed in
Pallas; dw/db are cross-row reductions XLA already fuses optimally.
``interpret=True`` runs the kernels on CPU for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_enabled

DEFAULT_BLOCK_N = 256


def available() -> bool:
    return kernel_enabled("use_pallas_layer_norm")


def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, m_ref, r_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    o_ref[...] = ((x - m) * r * w[None, :] + b[None, :]).astype(o_ref.dtype)
    m_ref[...] = m
    r_ref[...] = r


def _ln_bwd_kernel(x_ref, w_ref, m_ref, r_ref, g_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    m = m_ref[...]
    r = r_ref[...]
    g = g_ref[...].astype(jnp.float32)
    xhat = (x - m) * r
    wg = g * w[None, :]
    # dx = r * (wg - mean(wg) - xhat * mean(wg * xhat))
    mu1 = jnp.mean(wg, axis=-1, keepdims=True)
    mu2 = jnp.mean(wg * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (r * (wg - mu1 - xhat * mu2)).astype(dx_ref.dtype)


def _fwd(x2d, w, b, eps: float, block_n: int, interpret: bool):
    n, h = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False), jax.named_scope("ln_fwd"):
        out, m, r = pl.pallas_call(
            functools.partial(_ln_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,)),
                      pl.BlockSpec((h,), lambda i: (0,))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x2d.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
        )(x2d, w, b)
    return out, m, r


def _bwd_dx(x2d, w, m, r, g2d, block_n: int, interpret: bool):
    n, h = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False), jax.named_scope("ln_bwd"):
        return pl.pallas_call(
            _ln_bwd_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), x2d.dtype),
            interpret=interpret,
        )(x2d, w, m, r, g2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layer_norm_pallas(x, w, b, eps: float = 1e-5,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False):
    """y = (x - mean) * rsqrt(var + eps) * w + b over [..., H]."""
    out, _ = _ln_fwd(x, w, b, eps, block_n, interpret)
    return out


def _ln_fwd(x, w, b, eps, block_n, interpret):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    # b's dtype rides along as a zero-size array (residuals must be
    # JAX types; db's cotangent must match b's dtype exactly)
    b_tag = jnp.zeros((0,), b.dtype)
    if x2d.shape[0] == 0:   # empty batch: nothing to normalize
        zero = jnp.zeros((0, 1), jnp.float32)
        return x.reshape(shape), (x2d, w, b_tag, zero, zero)
    out, m, r = _fwd(x2d, w, b, eps, block_n, interpret)
    return out.reshape(shape), (x2d, w, b_tag, m, r)


def _ln_bwd(eps, block_n, interpret, res, g):
    x2d, w, b_tag, m, r = res
    b_dtype = b_tag.dtype
    g2d = g.reshape(x2d.shape)
    if x2d.shape[0] == 0:
        h = x2d.shape[-1]
        return (g2d.reshape(g.shape), jnp.zeros((h,), w.dtype),
                jnp.zeros((h,), b_dtype))
    dx = _bwd_dx(x2d, w, m, r, g2d, block_n, interpret)
    # dw/db: cross-row reductions — XLA's job
    g32 = g2d.astype(jnp.float32)
    xhat = (x2d.astype(jnp.float32) - m) * r
    dw = jnp.sum(g32 * xhat, axis=0).astype(w.dtype)
    db = jnp.sum(g32, axis=0).astype(b_dtype)
    return dx.reshape(g.shape), dw, db


layer_norm_pallas.defvjp(_ln_fwd, _ln_bwd)


def reference_layer_norm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - m), -1, keepdims=True)
    return (((xf - m) * jax.lax.rsqrt(var + eps))
            * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)
