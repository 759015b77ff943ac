"""Fused decode-step Pallas kernels (the MPK mega-kernel direction,
PAPERS.md arXiv 2512.22219): the per-token body of the compiled
``decode_loop`` is three kernel launches instead of a long chain of
small ops.

Kernels (all single-token decode shapes, composing the building blocks
already proven in ``rope.py`` / ``flash_attention.py`` / ``rms_norm.py``
/ ``layer_norm.py``):

* :func:`rope_qkv` — the q/k/v projections of ONE new token plus the
  rotary embedding at its position, in one kernel.  The pair rotation
  uses the same lane-roll + sign-mask trick as ``rope.py`` (no strided
  gathers); because rotation pairs never cross a head boundary
  (head_dim is even), the roll is applied to the flat ``[B, nh*hd]``
  projection with the cos/sin row tiled per head.
* :func:`attend_cache_append` — append the new k/v row into the
  preallocated ``[B, S_total, n_kv, hd]`` cache at ``pos`` and compute
  masked decode attention against the whole cache in the same kernel
  (GQA via a static per-kv-head loop, never materialised).  The cache
  outputs alias their inputs on the jit side (donated loop carries).
* :func:`norm_mlp` — the post-attention norm + MLP tail: LayerNorm +
  gelu MLP (GPT blocks) or RMSNorm + SwiGLU (LLaMA blocks).
* :func:`norm_matmul` — the claimable norm→matmul chain the program
  pass pipeline flags via ``fusion_hints`` (static/passes
  ``program_claim_fused_kernels`` rewrites flagged chains onto this).

Every kernel has a jnp reference composition that mirrors the eager
ops' numerics EXACTLY (same fp32 statistics, same ``-1e30`` mask
constant, same op order) — the compiled decode loop must be
token-for-token identical to the eager loop, so on backends where the
Pallas path is off the reference is the loop body.  Kernels trace
under ``enable_x64(False)`` and pin every literal (PTL603): this
package runs with jax_enable_x64 globally on, where an unpinned
constructor literal silently promotes to f64/i64 under an outer jit.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...flags import get_flag
from . import kernel_enabled

# VMEM budget gate: a kernel whose resident weights exceed this falls
# back to the reference composition (XLA streams it instead)
_VMEM_BUDGET_BYTES = 10 << 20


def available() -> bool:
    return kernel_enabled("use_pallas_fused_decode")


def _interpret() -> bool:
    return bool(get_flag("pallas_interpret"))


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


def _dims_ok(*dims) -> bool:
    return all(int(d) % 8 == 0 for d in dims)


# ---------------------------------------------------------------------------
# shared reference pieces — EXACT mirrors of the eager ops' numerics
# ---------------------------------------------------------------------------

def reference_rope_rows(x, cos_row, sin_row, neox: bool = False):
    """Rotate ``x [..., D]`` by one position row (``cos/sin [D]``) —
    the elementwise formula of incubate fused_rotary_position_embedding's
    jnp path."""
    if neox:
        x1, x2 = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-x2, x1], axis=-1)
    else:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
    return x * cos_row + rot * sin_row


def reference_rms_norm(v, w, eps: float):
    """Mirror of incubate fused_rms_norm's jnp path (fp32 variance,
    rsqrt cast back to the input dtype BEFORE the weight multiply)."""
    var = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return v * jax.lax.rsqrt(var + eps).astype(v.dtype) * w


def reference_layer_norm(v, w, b, eps: float):
    """Mirror of nn.functional.layer_norm's jnp path."""
    v32 = v.astype(jnp.float32)
    m = jnp.mean(v32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(v32 - m), axis=-1, keepdims=True)
    out = ((v32 - m) * jax.lax.rsqrt(var + eps)).astype(v.dtype)
    return out * w + b


# ---------------------------------------------------------------------------
# 1. fused rope + QKV projection
# ---------------------------------------------------------------------------

def _rope_flat(x, cos_t, sin_t, neox: bool, d: int):
    """Rotate flat ``[B, n*d]`` rows (cos/sin already head-tiled) with
    the rope.py lane-roll trick — pairs never cross head boundaries."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    if neox:
        half = d // 2
        seg = lane % jnp.int32(d)
        rolled = jnp.where(seg < jnp.int32(half),
                           jnp.roll(x, -half, axis=1),
                           jnp.roll(x, half, axis=1))
        rot = jnp.where(seg < jnp.int32(half), -rolled, rolled)
    else:
        rot = jnp.where(lane % jnp.int32(2) == jnp.int32(0),
                        -jnp.roll(x, -1, axis=1),
                        jnp.roll(x, 1, axis=1))
    return x * cos_t + rot * sin_t


def _rope_qkv_kernel(x_ref, wq_ref, wk_ref, wv_ref, bq_ref, bk_ref,
                     bv_ref, cq_ref, sq_ref, ck_ref, sk_ref,
                     q_ref, k_ref, v_ref, *, rope: bool, neox: bool,
                     d: int):
    x = x_ref[...].astype(jnp.float32)               # [B, H]
    q = jnp.dot(x, wq_ref[...].astype(jnp.float32)) + bq_ref[...]
    k = jnp.dot(x, wk_ref[...].astype(jnp.float32)) + bk_ref[...]
    v = jnp.dot(x, wv_ref[...].astype(jnp.float32)) + bv_ref[...]
    if rope:
        q = _rope_flat(q, cq_ref[...], sq_ref[...], neox, d)
        k = _rope_flat(k, ck_ref[...], sk_ref[...], neox, d)
    q_ref[...] = q.astype(q_ref.dtype)
    k_ref[...] = k.astype(k_ref.dtype)
    v_ref[...] = v.astype(v_ref.dtype)


def _rope_qkv_pallas(x, wq, wk, wv, bq, bk, bv, cos_row, sin_row,
                     n_heads, n_kv, head_dim, neox):
    b, h = x.shape
    dq, dk = n_heads * head_dim, n_kv * head_dim
    rope = cos_row is not None
    if rope:
        cq = jnp.tile(cos_row.astype(jnp.float32), n_heads).reshape(1, dq)
        sq = jnp.tile(sin_row.astype(jnp.float32), n_heads).reshape(1, dq)
        ck = jnp.tile(cos_row.astype(jnp.float32), n_kv).reshape(1, dk)
        sk = jnp.tile(sin_row.astype(jnp.float32), n_kv).reshape(1, dk)
    else:
        cq = jnp.ones((1, dq), jnp.float32)
        sq = jnp.zeros((1, dq), jnp.float32)
        ck = jnp.ones((1, dk), jnp.float32)
        sk = jnp.zeros((1, dk), jnp.float32)
    zq = jnp.zeros((1, dq), jnp.float32) if bq is None \
        else bq.astype(jnp.float32).reshape(1, dq)
    zk = jnp.zeros((1, dk), jnp.float32) if bk is None \
        else bk.astype(jnp.float32).reshape(1, dk)
    zv = jnp.zeros((1, dk), jnp.float32) if bv is None \
        else bv.astype(jnp.float32).reshape(1, dk)
    full = lambda *shape: pl.BlockSpec(shape, lambda: tuple(
        0 for _ in shape))
    with jax.enable_x64(False):
        q, k, v = pl.pallas_call(
            functools.partial(_rope_qkv_kernel, rope=rope, neox=neox,
                              d=head_dim),
            grid=(),
            in_specs=[full(b, h), full(h, dq), full(h, dk), full(h, dk),
                      full(1, dq), full(1, dk), full(1, dk),
                      full(1, dq), full(1, dq), full(1, dk), full(1, dk)],
            out_specs=[full(b, dq), full(b, dk), full(b, dk)],
            out_shape=[jax.ShapeDtypeStruct((b, dq), x.dtype),
                       jax.ShapeDtypeStruct((b, dk), x.dtype),
                       jax.ShapeDtypeStruct((b, dk), x.dtype)],
            interpret=_interpret(),
        )(x, wq, wk, wv, zq, zk, zv, cq, sq, ck, sk)
    return (q.reshape(b, n_heads, head_dim),
            k.reshape(b, n_kv, head_dim),
            v.reshape(b, n_kv, head_dim))


def _rope_qkv_reference(x, wq, wk, wv, bq, bk, bv, cos_row, sin_row,
                        n_heads, n_kv, head_dim, neox):
    b = x.shape[0]
    q = jnp.matmul(x, wq)
    k = jnp.matmul(x, wk)
    v = jnp.matmul(x, wv)
    if bq is not None:
        q = q + bq
    if bk is not None:
        k = k + bk
    if bv is not None:
        v = v + bv
    q = q.reshape(b, n_heads, head_dim)
    k = k.reshape(b, n_kv, head_dim)
    v = v.reshape(b, n_kv, head_dim)
    if cos_row is not None:
        q = reference_rope_rows(q, cos_row, sin_row, neox)
        k = reference_rope_rows(k, cos_row, sin_row, neox)
    return q, k, v


def rope_qkv(x, wq, wk, wv, bq=None, bk=None, bv=None, cos_row=None,
             sin_row=None, *, n_heads, n_kv, head_dim, neox=False):
    """Fused q/k/v projection (+ optional rotary embedding) of one
    decode token.  ``x [B, H]``; ``w* [H, n*hd]``; ``cos/sin [hd]``
    (None: no rope — GPT's learned positions live in the embedding).
    Returns ``(q [B, nh, hd], k [B, nkv, hd], v [B, nkv, hd])``."""
    if available() and head_dim % 2 == 0 \
            and _dims_ok(x.shape[1], n_heads * head_dim,
                         n_kv * head_dim) \
            and _nbytes(wq, wk, wv) <= _VMEM_BUDGET_BYTES:
        return _rope_qkv_pallas(x, wq, wk, wv, bq, bk, bv, cos_row,
                                sin_row, n_heads, n_kv, head_dim, neox)
    return _rope_qkv_reference(x, wq, wk, wv, bq, bk, bv, cos_row,
                               sin_row, n_heads, n_kv, head_dim, neox)


# ---------------------------------------------------------------------------
# 2. fused attention + cache append
# ---------------------------------------------------------------------------

def _attend_kernel(q_ref, kn_ref, vn_ref, kc_ref, vc_ref, pos_ref,
                   ctx_ref, ko_ref, vo_ref, *, n_rep: int, n_kv: int,
                   scale: float):
    pos = pos_ref[0, 0]
    kc = kc_ref[0]                                   # [St, nkv, hd]
    vc = vc_ref[0]
    st = kc.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (st, 1, 1), 0)
    kc = jnp.where(row == pos, kn_ref[0][None].astype(kc.dtype), kc)
    vc = jnp.where(row == pos, vn_ref[0][None].astype(vc.dtype), vc)
    ko_ref[0] = kc
    vo_ref[0] = vc
    q = q_ref[0].astype(jnp.float32)                 # [nh, hd]
    mask = jax.lax.broadcasted_iota(jnp.int32, (1, st), 1) <= pos
    outs = []
    for g in range(n_kv):                            # static GQA loop
        qg = q[g * n_rep:(g + 1) * n_rep]            # [n_rep, hd]
        kg = kc[:, g].astype(jnp.float32)            # [St, hd]
        vg = vc[:, g].astype(jnp.float32)
        s = jax.lax.dot_general(qg, kg, (((1,), (1,)), ((), ())))
        s = jnp.where(mask, s * jnp.float32(scale), jnp.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jax.lax.dot_general(p, vg, (((1,), (0,)), ((), ()))))
    ctx_ref[0] = jnp.concatenate(outs, axis=0).astype(ctx_ref.dtype)


def _attend_pallas(q, k_new, v_new, k_cache, v_cache, pos, scale):
    b, nh, hd = q.shape
    _, st, nkv, _ = k_cache.shape
    pos2d = jnp.asarray(pos, jnp.int32).reshape(1, 1)
    row3 = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda i: (i,) + tuple(0 for _ in shape))
    with jax.enable_x64(False):
        ctx, kc, vc = pl.pallas_call(
            functools.partial(_attend_kernel, n_rep=nh // nkv,
                              n_kv=nkv, scale=scale),
            grid=(b,),
            in_specs=[row3(nh, hd), row3(nkv, hd), row3(nkv, hd),
                      row3(st, nkv, hd), row3(st, nkv, hd),
                      pl.BlockSpec((1, 1), lambda i: (0, 0))],
            out_specs=[row3(nh, hd), row3(st, nkv, hd),
                       row3(st, nkv, hd)],
            out_shape=[jax.ShapeDtypeStruct((b, nh, hd), q.dtype),
                       jax.ShapeDtypeStruct(k_cache.shape,
                                            k_cache.dtype),
                       jax.ShapeDtypeStruct(v_cache.shape,
                                            v_cache.dtype)],
            input_output_aliases={3: 1, 4: 2},
            interpret=_interpret(),
        )(q, k_new, v_new, k_cache, v_cache, pos2d)
    return ctx, kc, vc


def _attend_reference(q, k_new, v_new, k_cache, v_cache, pos, scale):
    """Mirror of the eager decode step: cache append + sdpa's XLA path
    (fp32 logits, ``-1e30`` mask constant, fp32 softmax cast back)."""
    b, nh, hd = q.shape
    _, st, nkv, _ = k_cache.shape
    zero = jnp.int32(0)
    p32 = jnp.asarray(pos, jnp.int32)
    kc = jax.lax.dynamic_update_slice(
        k_cache, k_new[:, None].astype(k_cache.dtype),
        (zero, p32, zero, zero))
    vc = jax.lax.dynamic_update_slice(
        v_cache, v_new[:, None].astype(v_cache.dtype),
        (zero, p32, zero, zero))
    rep = nh // nkv
    k = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
    v = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
    qt = q[:, :, None]                               # [B, nh, 1, hd]
    kt = jnp.swapaxes(k, 1, 2)                       # [B, nh, St, hd]
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt).astype(jnp.float32) \
        * jnp.float32(scale)
    valid = jnp.arange(st, dtype=jnp.int32) <= p32
    logits = jnp.where(valid[None, None, None, :], logits,
                       jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhst,bhtd->bhsd", probs, vt)   # [B, nh, 1, hd]
    return ctx[:, :, 0], kc, vc


def attend_cache_append(q, k_new, v_new, k_cache, v_cache, pos,
                        scale=None):
    """Append one token's k/v into the preallocated cache at ``pos``
    and attend ``q`` against the valid prefix, in one fused kernel.

    ``q [B, nh, hd]``; ``k_new/v_new [B, nkv, hd]``; caches
    ``[B, S_total, nkv, hd]``; ``pos`` scalar int32 (device tracer ok).
    Returns ``(ctx [B, nh, hd], k_cache', v_cache')`` — the cache
    outputs alias the inputs under the Pallas path so the jit can
    donate them as loop carries."""
    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    nh, nkv = q.shape[1], k_cache.shape[2]
    if available() and nh % nkv == 0 and _dims_ok(hd) \
            and _nbytes(k_cache[0], v_cache[0]) <= _VMEM_BUDGET_BYTES:
        return _attend_pallas(q, k_new, v_new, k_cache, v_cache, pos,
                              float(scale))
    return _attend_reference(q, k_new, v_new, k_cache, v_cache, pos,
                             float(scale))


# ---------------------------------------------------------------------------
# 3. fused norm + MLP
# ---------------------------------------------------------------------------

def _norm_mlp_kernel(x_ref, nw_ref, nb_ref, w1_ref, b1_ref, w2_ref,
                     b2_ref, wg_ref, o_ref, *, kind: str, eps: float,
                     act: str):
    x = x_ref[...]
    x32 = x.astype(jnp.float32)
    nw = nw_ref[...].astype(jnp.float32)
    if kind == "layer_norm":
        m = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - m), axis=-1, keepdims=True)
        h = (x32 - m) * jax.lax.rsqrt(var + eps) * nw \
            + nb_ref[...].astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        h = x32 * jax.lax.rsqrt(var + eps) * nw
    w1 = w1_ref[...].astype(jnp.float32)
    a = jnp.dot(h, w1) + b1_ref[...]
    if kind == "layer_norm":
        a = jax.nn.gelu(a, approximate=(act == "gelu_tanh"))
        y = jnp.dot(a, w2_ref[...].astype(jnp.float32)) + b2_ref[...]
    else:
        g = jnp.dot(h, wg_ref[...].astype(jnp.float32))
        g = jax.nn.gelu(g, approximate=True) if act == "gelu_tanh" \
            else jax.nn.silu(g)
        y = jnp.dot(g * a, w2_ref[...].astype(jnp.float32))
    o_ref[...] = y.astype(o_ref.dtype)


def _norm_mlp_pallas(x, kind, norm_w, norm_b, w1, b1, w2, b2, w_gate,
                     eps, act):
    b, h = x.shape
    inter = w1.shape[1]
    out_dim = w2.shape[1]
    f32 = jnp.float32
    nb = jnp.zeros((1, h), f32) if norm_b is None \
        else norm_b.astype(f32).reshape(1, h)
    z1 = jnp.zeros((1, inter), f32) if b1 is None \
        else b1.astype(f32).reshape(1, inter)
    z2 = jnp.zeros((1, out_dim), f32) if b2 is None \
        else b2.astype(f32).reshape(1, out_dim)
    wg = jnp.zeros((1, 1), x.dtype) if w_gate is None else w_gate
    full = lambda *shape: pl.BlockSpec(shape, lambda: tuple(
        0 for _ in shape))
    with jax.enable_x64(False):
        return pl.pallas_call(
            functools.partial(_norm_mlp_kernel, kind=kind,
                              eps=float(eps), act=act),
            grid=(),
            in_specs=[full(b, h), full(h,), full(1, h),
                      full(*w1.shape), full(1, inter),
                      full(*w2.shape), full(1, out_dim),
                      full(*wg.shape)],
            out_specs=full(b, out_dim),
            out_shape=jax.ShapeDtypeStruct((b, out_dim), x.dtype),
            interpret=_interpret(),
        )(x, norm_w, nb, w1, z1, w2, z2, wg)


def _norm_mlp_reference(x, kind, norm_w, norm_b, w1, b1, w2, b2,
                        w_gate, eps, act):
    if kind == "layer_norm":
        h = reference_layer_norm(x, norm_w, norm_b, eps)
        a = jnp.matmul(h, w1)
        if b1 is not None:
            a = a + b1
        a = jax.nn.gelu(a, approximate=(act == "gelu_tanh"))
        y = jnp.matmul(a, w2)
        return y + b2 if b2 is not None else y
    h = reference_rms_norm(x, norm_w, eps)
    g = jnp.matmul(h, w_gate)
    g = jax.nn.gelu(g, approximate=True) if act == "gelu_tanh" \
        else jax.nn.silu(g)
    u = jnp.matmul(h, w1)
    return jnp.matmul(g * u, w2)


def norm_mlp(x, *, kind, norm_w, norm_b=None, w1, b1=None, w2, b2=None,
             w_gate=None, eps=1e-5, act="silu"):
    """Fused norm + MLP tail of one decoder block on ``x [B, H]``.

    ``kind='layer_norm'``: LayerNorm → ``w1``/``b1`` → gelu →
    ``w2``/``b2`` (GPT).  ``kind='rms_norm'``: RMSNorm → SwiGLU
    (``w_gate``/``w1``=up/``w2``=down, LLaMA).  Residual adds stay
    outside (they mirror the eager block structure)."""
    if kind not in ("layer_norm", "rms_norm"):
        raise ValueError(f"unknown norm kind {kind!r}")
    if available() and _dims_ok(x.shape[1], w1.shape[1], w2.shape[1]) \
            and _nbytes(w1, w2, *(() if w_gate is None else (w_gate,))) \
            <= _VMEM_BUDGET_BYTES:
        return _norm_mlp_pallas(x, kind, norm_w, norm_b, w1, b1, w2, b2,
                                w_gate, eps, act)
    return _norm_mlp_reference(x, kind, norm_w, norm_b, w1, b1, w2, b2,
                               w_gate, eps, act)


# ---------------------------------------------------------------------------
# 4. claimable norm + matmul (program_claim_fused_kernels target)
# ---------------------------------------------------------------------------

def _norm_matmul_kernel(x_ref, nw_ref, nb_ref, w_ref, o_ref, *,
                        kind: str, eps: float):
    x32 = x_ref[...].astype(jnp.float32)
    nw = nw_ref[...].astype(jnp.float32)
    if kind == "layer_norm":
        m = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - m), axis=-1, keepdims=True)
        h = (x32 - m) * jax.lax.rsqrt(var + eps) * nw \
            + nb_ref[...].astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        h = x32 * jax.lax.rsqrt(var + eps) * nw
    o_ref[...] = jnp.dot(h, w_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def norm_matmul_supported(h: int, n: int, w_bytes: int) -> bool:
    return (available() and _dims_ok(h, n)
            and w_bytes <= _VMEM_BUDGET_BYTES)


def norm_matmul(x, norm_w, norm_b, w, bias=None, *, kind="rms_norm",
                eps=1e-6):
    """Fused ``matmul(norm(x), w) (+ bias)`` over ``x [..., H]`` with
    ``w [H, N]`` (claim sites pre-transpose ``transpose_y`` weights).
    Routes to one Pallas kernel when available, else the reference
    composition mirroring the captured ops' numerics."""
    shape = x.shape
    h, n = w.shape
    x2d = x.reshape(-1, h)
    if norm_matmul_supported(h, n, _nbytes(w)) and x2d.shape[0] > 0:
        nb = jnp.zeros((1, h), jnp.float32) if norm_b is None \
            else norm_b.astype(jnp.float32).reshape(1, h)
        full = lambda *s: pl.BlockSpec(s, lambda: tuple(0 for _ in s))
        with jax.enable_x64(False):
            out = pl.pallas_call(
                functools.partial(_norm_matmul_kernel, kind=kind,
                                  eps=float(eps)),
                grid=(),
                in_specs=[full(*x2d.shape), full(h,), full(1, h),
                          full(h, n)],
                out_specs=full(x2d.shape[0], n),
                out_shape=jax.ShapeDtypeStruct((x2d.shape[0], n),
                                               x.dtype),
                interpret=_interpret(),
            )(x2d, norm_w, nb, w)
    else:
        if kind == "layer_norm":
            hn = reference_layer_norm(x2d, norm_w, norm_b, eps)
        else:
            hn = reference_rms_norm(x2d, norm_w, eps)
        out = jnp.matmul(hn, w)
    out = out.reshape(shape[:-1] + (n,))
    return out + bias if bias is not None else out
