"""f32 flash-attention tolerance: the error budget, derived and measured
(VERDICT r4 item 6 — "decide mathematically whether the bound or the
kernel is wrong").

THE BOUND.  On TPU, a DEFAULT-precision f32 matmul does not multiply
f32 numbers: the MXU quantizes each operand to bf16 (8-bit mantissa)
for the product pass, accumulating in f32.  A single quantization has
relative error ≤ 2^-9 per operand (round-to-nearest half-ULP of an
8-bit mantissa), so one product carries ≲ 2·2^-9 ≈ 3.9e-3 relative.
The Pallas flash kernel and the XLA reference attention BOTH run their
q·k and p·v products this way but with different tilings and
reduction orders, so their outputs each sit within ~3.9e-3 of the true
f32 result and within |a-exact| + |b-exact| ≈ 8e-3 of each other.
That is the forward tolerance in tools/tpu_kernel_parity.py — the
KERNEL is not wrong; 1e-6-class tolerances were (they assume f32
products the hardware never performs at DEFAULT precision).

Backward stacks two more matmul stages (dp = g·v, dq/dk from dp) on a
recomputed softmax, roughly tripling the independent quantization
noise: the harness's 5× slack (4e-2) covers it with margin.

THE MEASUREMENT.  This script reproduces the budget WITHOUT hardware:
it compares exact-f64 attention against attention whose matmul inputs
are bf16-quantized per product pass (the MXU model), for two different
reduction orders, and prints the observed pairwise deviation.  Run it
anywhere; on TPU it also measures kernel-vs-XLA directly.

Empirically (this script, 512x512x128, seed 0): one-shot pipeline
4.1e-3 vs exact, online pipeline 3.6e-3 vs exact, pairwise 2.1e-3 —
in line with the 4e-3 to 6e-3 the flash and ragged kernels show against
their highest-precision references on a v5e (chip_smoke.py, PR 21).  The
8e-3 bound holds with ~2-4x headroom; anything materially tighter
(e.g. 2e-3) would sit inside the noise and flake.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np


def _bf16(x):
    """Round-to-nearest-even bf16 (the MXU operand path), returned in
    f64 so later arithmetic is exact — via the u32 view so numpy needs
    no bfloat16 dtype."""
    u = np.asarray(x, np.float32).view(np.uint32)
    rounded = ((u.astype(np.uint64) + 0x7FFF + ((u >> 16) & 1)) &
               0xFFFF0000).astype(np.uint32)
    return rounded.view(np.float32).astype(np.float64)


def mxu_matmul(a, b):
    """DEFAULT-precision TPU matmul model: bf16 operands, f32 accum."""
    return np.asarray(
        _bf16(a) @ _bf16(b), np.float32).astype(np.float64)


def attention(q, k, v, matmul, online=False):
    """Pipeline A: one-shot softmax (the XLA lowering shape).
    Pipeline B (online=True): blockwise online softmax with running
    max/denominator rescaling in f32 — the flash kernel's accumulation
    order.  All softmax intermediates round through f32 in both, as on
    hardware; only the ORDER differs."""
    f32 = lambda a: np.asarray(a, np.float32).astype(np.float64)
    scale = 1.0 / np.sqrt(q.shape[-1])
    if not online:
        s = f32(matmul(q, k.T) * scale)
        s = f32(s - s.max(axis=-1, keepdims=True))
        p = f32(np.exp(np.asarray(s, np.float32)))
        denom = f32(p.sum(axis=-1, keepdims=True))
        return f32(matmul(f32(p / denom), v))
    nblk = 4
    ks = np.array_split(k, nblk)
    vs = np.array_split(v, nblk)
    m = np.full((q.shape[0], 1), -np.inf)
    l = np.zeros((q.shape[0], 1))
    acc = np.zeros((q.shape[0], v.shape[-1]))
    for kb, vb in zip(ks, vs):
        s = f32(matmul(q, kb.T) * scale)
        m_new = f32(np.maximum(m, s.max(axis=-1, keepdims=True)))
        alpha = f32(np.exp(np.asarray(m - m_new, np.float32)))
        p = f32(np.exp(np.asarray(s - m_new, np.float32)))
        l = f32(l * alpha + p.sum(axis=-1, keepdims=True))
        acc = f32(acc * alpha + matmul(p, vb))
        m = m_new
    return f32(acc / l)


def main():
    rs = np.random.RandomState(0)
    sq, sk, d = 512, 512, 128
    q = rs.randn(sq, d)
    k = rs.randn(sk, d)
    v = rs.randn(sk, d)

    exact = attention(q, k, v, lambda a, b: a @ b)
    pipe_a = attention(q, k, v, mxu_matmul)
    pipe_b = attention(q, k, v, mxu_matmul, online=True)

    def rel(a, b):
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))

    print(f"pipeline A vs exact : {rel(pipe_a, exact):.2e}")
    print(f"pipeline B vs exact : {rel(pipe_b, exact):.2e}")
    print(f"A vs B (the parity measurement): {rel(pipe_a, pipe_b):.2e}")
    print("budget: each pipeline <= ~3.9e-3 (one bf16 product pass); "
          "pairwise <= ~8e-3  -> harness fwd tol 8e-3, bwd 5x")

    # opt-in: the analysis above is numpy-only; the on-chip cross-check
    # initializes the default backend and needs the chip
    if os.environ.get("FLASH_ANALYZE_TPU") != "1":
        return
    import jax
    if jax.default_backend() == "tpu":
        import jax.numpy as jnp
        from paddle_tpu.ops.flash_attention import (
            flash_attention_bhsd, reference_attention_bhsd)
        qj = jnp.asarray(q[None], jnp.float32)
        kj = jnp.asarray(k[None], jnp.float32)
        vj = jnp.asarray(v[None], jnp.float32)
        o1 = flash_attention_bhsd(qj, kj, vj, 1.0 / np.sqrt(d), True,
                                  128, 128, False, 0, 1)
        o2 = reference_attention_bhsd(qj, kj, vj, 1.0 / np.sqrt(d), True)
        print(f"on-TPU kernel vs XLA: {rel(np.asarray(o1), np.asarray(o2)):.2e}")


if __name__ == "__main__":
    main()
