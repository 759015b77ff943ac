"""Minimal repro for the GQA-backward Mosaic compile hang (staged for
ROADMAP S3): an early attempt to compile the dkv backward kernel of the
GQA flash path for a v5e never finished, and the GQA Pallas path has
been gated off since (opt-in via FLAGS_pallas_gqa /
TPU_PARITY_GQA_BWD=1).

What this script does, smallest first:
  1. interpret-mode sanity (CPU): the exact failing configuration
     computes correct grads under the Pallas interpreter — the bug is
     in Mosaic LOWERING, not kernel math.
  2. (TPU, opt-in GQA_REPRO_COMPILE=1) lower-and-compile ONLY the dkv
     backward kernel at descending sizes, printing progress before
     each attempt so the hang point is identifiable in the log.  Give
     the chip call a timeout: a hang is then a 124, not a lost machine.

The failing config on record:
  bf16, bh=16, sq=sk=512, d=128, causal, n_rep=4
  block_q=block_k=128  -> dkv grid iterates q-blocks INSIDE k-blocks
  with an n_rep-strided head mapping — the suspected trigger is the
  strided head indexing in the dkv accumulation loop.

Usage:
  python tools/gqa_bwd_repro.py                     # interpret sanity
  GQA_REPRO_COMPILE=1 python tools/gqa_bwd_repro.py   # on the chip
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COMPILE = os.environ.get("GQA_REPRO_COMPILE") == "1"
if not COMPILE:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import jax
if not COMPILE:
    jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np

from paddle_tpu import flags
from paddle_tpu.ops.flash_attention import (flash_attention_bhsd,
                                            reference_attention_bhsd)

CASES = [
    # (tag, bh, sq, sk, d, n_rep, block) — first is the exact hang
    ("full-size", 16, 512, 512, 128, 4, 128),
    ("half-seq", 16, 256, 256, 128, 4, 128),
    ("quarter-seq", 8, 128, 128, 128, 4, 128),
    ("tiny", 4, 128, 128, 128, 2, 128),
]


def grads(case, interpret):
    tag, bh, sq, sk, d, n_rep, blk = case
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (bh, sq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (bh // n_rep, sk, d), jnp.bfloat16)
    v = jax.random.normal(kv, (bh // n_rep, sk, d), jnp.bfloat16)
    g = jax.random.normal(kg, (bh, sq, d), jnp.bfloat16)
    scale = 1.0 / np.sqrt(d)

    def loss(q, k, v):
        o = flash_attention_bhsd(q, k, v, scale, True, blk, blk,
                                 interpret, 0, n_rep)
        return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))

    def loss_ref(q, k, v):
        k2 = jnp.repeat(k, n_rep, axis=0)
        v2 = jnp.repeat(v, n_rep, axis=0)
        o = reference_attention_bhsd(q, k2, v2, scale, True)
        return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))

    dq, dk, dv = jax.grad(loss, (0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, a, b in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32)))
                    / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-9))
        status = "OK" if err < 0.1 else "MISMATCH"
        print(f"  [{tag}] {name} rel_err={err:.4f} {status}", flush=True)


def main():
    flags.set_flags({"FLAGS_use_pallas_attention": True,
                     "FLAGS_pallas_gqa": True})
    if not COMPILE:
        print("interpret-mode sanity (CPU) — kernel MATH for the exact "
              "Mosaic-failing configs:", flush=True)
        for case in CASES:
            grads(case, interpret=True)
        print("all interpret checks done: the hang is a Mosaic lowering "
              "issue, not kernel math")
        return
    print("COMPILE MODE on", jax.devices()[0], "- smallest case first; "
          "each line prints BEFORE the attempt so the hang point is "
          "identifiable.",
          flush=True)
    for case in reversed(CASES):
        print(f"compiling {case[0]} ...", flush=True)
        t0 = time.time()
        grads(case, interpret=False)
        print(f"  {case[0]} compiled+ran in {time.time()-t0:.1f}s",
              flush=True)
    print("NO HANG REPRODUCED — consider re-enabling the GQA gate "
          "(FLAGS_pallas_gqa default)")


if __name__ == "__main__":
    main()
