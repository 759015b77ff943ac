"""Pallas fused softmax cross-entropy (ref: paddle/phi/kernels/gpu/
cross_entropy_kernel.cu + fusion/fused_softmax_mask — the LM-loss hot
path: for GPT-class vocabularies the [N, V] softmax+gather dominates
the loss computation).

One VMEM pass per row block computes the stable logsumexp AND the
picked-label logit (as an iota-compare one-hot contraction — gathers
lower poorly on the VPU, masked reductions don't); the saved lse drives
the hand-written backward ``dx = softmax(x) - onehot`` without
rematerializing the softmax.  ``ignore_index`` rows produce zero loss
and zero gradient in-kernel.  ``interpret=True`` runs on CPU for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_enabled

DEFAULT_BLOCK_N = 16      # x (bn, V) fp32 in VMEM: 16 x 50304 x 4 = 3.2MB


def available() -> bool:
    return kernel_enabled("use_pallas_softmax_ce")


def _fwd_kernel(x_ref, lab_ref, o_ref, lse_ref, *, ignore_index: int):
    x = x_ref[...].astype(jnp.float32)               # (bn, V)
    lab = lab_ref[...]                               # (bn, 1) int32
    bn, v = x.shape
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, v), 1)
    onehot = (cols == safe).astype(jnp.float32)
    picked = jnp.sum(x * onehot, axis=-1, keepdims=True)
    loss = jnp.where(valid, lse - picked, 0.0)
    o_ref[...] = loss
    lse_ref[...] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *,
                ignore_index: int):
    x = x_ref[...].astype(jnp.float32)
    lab = lab_ref[...]
    lse = lse_ref[...]
    g = g_ref[...]                                    # (bn, 1) f32
    bn, v = x.shape
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, v), 1)
    onehot = (cols == safe).astype(jnp.float32)
    p = jnp.exp(x - lse)
    dx = (p - onehot) * jnp.where(valid, g, 0.0)
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _fwd(x2d, lab2d, ignore_index, block_n, interpret):
    n, v = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, ignore_index=ignore_index),
            grid=grid,
            in_specs=[pl.BlockSpec((bn, v), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
        )(x2d, lab2d)


def _bwd(x2d, lab2d, lse, g, ignore_index, block_n, interpret):
    n, v = x2d.shape
    bn = min(block_n, n)
    grid = (pl.cdiv(n, bn),)
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, ignore_index=ignore_index),
            grid=grid,
            in_specs=[pl.BlockSpec((bn, v), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, v), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, v), x2d.dtype),
            interpret=interpret,
        )(x2d, lab2d, lse, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def softmax_ce_pallas(logits2d, labels, ignore_index: int = -100,
                      block_n: int = DEFAULT_BLOCK_N,
                      interpret: bool = False):
    """Per-row loss (N,) = logsumexp(x) - x[label]; 0 for ignored rows.
    logits2d (N, V) float; labels (N,) int."""
    out, _ = _ce_fwd(logits2d, labels, ignore_index, block_n, interpret)
    return out


def _ce_fwd(logits2d, labels, ignore_index, block_n, interpret):
    lab2d = labels.astype(jnp.int32).reshape(-1, 1)
    loss, lse = _fwd(logits2d, lab2d, ignore_index, block_n, interpret)
    return loss[:, 0], (logits2d, lab2d, lse)


def _ce_bwd(ignore_index, block_n, interpret, res, g):
    logits2d, lab2d, lse = res
    g2d = g.astype(jnp.float32).reshape(-1, 1)
    dx = _bwd(logits2d, lab2d, lse, g2d, ignore_index, block_n,
              interpret)
    # integer primals take float0 cotangents by JAX convention (ADVICE
    # r4): an int32 zeros array only works under version-specific
    # leniency of the pinned jax
    import numpy as np
    return dx, np.zeros(lab2d.shape[0], jax.dtypes.float0)


softmax_ce_pallas.defvjp(_ce_fwd, _ce_bwd)


def reference_softmax_ce(logits2d, labels, ignore_index: int = -100):
    x = logits2d.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    lab = labels.astype(jnp.int32)
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    picked = jnp.take_along_axis(x, safe[:, None], axis=-1)[:, 0]
    return jnp.where(valid, lse - picked, 0.0)
