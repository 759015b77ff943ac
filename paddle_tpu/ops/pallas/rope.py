"""Fused rotary position embedding — Pallas kernel.

ref: paddle/phi/kernels/fusion/fused_rope (one CUDA kernel applying the
rotation to q/k in place).  TPU-native: one kernel per tensor over
[B*H, S, D] blocks; the pair-rotation is expressed as lane rolls + a
sign mask (no strided gathers, which Mosaic can't tile):

- interleaved (use_neox_rotary_style=False):
  rot[2i] = -x[2i+1], rot[2i+1] = x[2i]
  = where(lane even, -roll(x, -1), roll(x, +1))
- neox (half-split): rot[:d/2] = -x[d/2:], rot[d/2:] = x[:d/2]
  = where(lane < d/2, -roll(x, d/2), roll(x, d/2))

out = x * cos + rot * sin.  Both conventions repeat each frequency
across the rotated pair, so sin commutes with the pair permutation and
the VJP is the SAME kernel with sin negated (the rotation transpose) —
rope is linear in x.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import kernel_enabled


def available() -> bool:
    return kernel_enabled("use_pallas_rope")


def supports(d: int) -> bool:
    return d % 2 == 0 and d % 8 == 0


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, neox: bool, d: int):
    x = x_ref[0].astype(jnp.float32)          # [BS, D]
    c = cos_ref[...].astype(jnp.float32)      # [BS, D]
    s = sin_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if neox:
        half = jnp.roll(x, d // 2, axis=1)
        rot = jnp.where(lane < d // 2, -half, half)
    else:
        rot = jnp.where(lane % 2 == 0,
                        -jnp.roll(x, -1, axis=1),
                        jnp.roll(x, 1, axis=1))
    o_ref[0] = (x * c + rot * s).astype(o_ref.dtype)


def _rope_call(x, cos, sin, neox: bool, block_s: int, interpret: bool):
    """x: [BH, S, D]; cos/sin: [S, D]."""
    bh, s, d = x.shape
    bs = min(block_s, s)
    grid = (bh, pl.cdiv(s, bs))
    return pl.pallas_call(
        functools.partial(_rope_kernel, neox=neox, d=d),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bs, d), lambda b, i: (i, 0)),
            pl.BlockSpec((bs, d), lambda b, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), x.dtype),
        interpret=interpret,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def rope_bhsd(x, cos, sin, neox: bool, block_s: int = 256,
              interpret: bool = False):
    """Rotary embedding over [B*H, S, D] (cos/sin [S, D])."""
    with jax.enable_x64(False):
        return _rope_call(x, cos, sin, neox, block_s, interpret)


def _rope_fwd(x, cos, sin, neox, block_s, interpret):
    with jax.enable_x64(False):
        out = _rope_call(x, cos, sin, neox, block_s, interpret)
    return out, (cos, sin)


def _rope_bwd(neox, block_s, interpret, res, g):
    # cos/sin are precomputed position tables (never trained) — their
    # cotangents are declared zero
    cos, sin = res
    with jax.enable_x64(False):
        dx = _rope_call(g, cos, -sin, neox, block_s, interpret)
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


rope_bhsd.defvjp(_rope_fwd, _rope_bwd)


def reference_rope(x, cos, sin, neox: bool):
    """jnp oracle matching incubate fused_rotary_position_embedding."""
    if neox:
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
    else:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
    return x * cos + rot * sin
