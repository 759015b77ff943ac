"""On-hardware Pallas kernel parity (staged for ROADMAP S3).

Runs every Pallas kernel fwd+bwd on the REAL TPU (no interpret mode) and
compares against the jnp references. One JSON line per check; a final
summary line; the same records land in chiprun_out/KERNEL_PARITY.json,
rewritten after every check so a run that dies keeps what it proved.

Usage (through the chip tool): python tools/tpu_kernel_parity.py
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

RESULTS = []
INFO = {}
ARTIFACT = os.path.join(_REPO_ROOT, "chiprun_out", "KERNEL_PARITY.json")


def _persist(complete=False):
    n_ok = sum(1 for r in RESULTS if r.get("ok"))
    os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
    with open(ARTIFACT, "w") as fh:
        json.dump({**INFO, "ok": n_ok, "total": len(RESULTS),
                   "all_ok": n_ok == len(RESULTS),
                   "complete": complete, "results": RESULTS}, fh)


def check(name, got, want, tol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))
    ok = bool(err <= tol)
    rec = {"check": name, "ok": ok, "rel_err": round(err, 6), "tol": tol}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    _persist()
    return ok


def run(name, fn):
    t0 = time.time()
    try:
        fn()
        print(json.dumps({"kernel": name, "status": "done",
                          "t": round(time.time() - t0, 1)}), flush=True)
    except Exception as e:  # noqa: BLE001 - record, keep going
        RESULTS.append({"check": name, "ok": False, "err": repr(e)[:400]})
        print(json.dumps({"kernel": name, "status": "error",
                          "err": repr(e)[:400],
                          "t": round(time.time() - t0, 1)}), flush=True)
        _persist()


def rms_norm():
    from paddle_tpu.ops.pallas.rms_norm import rms_norm_pallas, reference_rms_norm
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (512, 1024), dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (1024,), dtype) * 0.1 + 1.0
        g = jax.random.normal(jax.random.PRNGKey(2), (512, 1024), dtype)

        out = rms_norm_pallas(x, w)
        ref = reference_rms_norm(x, w)
        check(f"rms_norm.fwd.{dtype.__name__}", out, ref, tol)

        def loss_p(x, w):
            return jnp.sum(rms_norm_pallas(x, w) * g.astype(jnp.float32))

        def loss_r(x, w):
            return jnp.sum(reference_rms_norm(x, w) * g.astype(jnp.float32))

        dxp, dwp = jax.grad(loss_p, (0, 1))(x, w)
        dxr, dwr = jax.grad(loss_r, (0, 1))(x, w)
        check(f"rms_norm.dx.{dtype.__name__}", dxp, dxr, tol * 4)
        check(f"rms_norm.dw.{dtype.__name__}", dwp, dwr, tol * 4)


def layer_norm():
    from paddle_tpu.ops.pallas.layer_norm import (layer_norm_pallas,
                                                  reference_layer_norm)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        x = jax.random.normal(jax.random.PRNGKey(0), (512, 1024), dtype)
        w = jax.random.normal(jax.random.PRNGKey(1), (1024,), dtype) * 0.1 + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (1024,), dtype) * 0.1
        g = jax.random.normal(jax.random.PRNGKey(3), (512, 1024), dtype)

        out = layer_norm_pallas(x, w, b)
        ref = reference_layer_norm(x, w, b)
        check(f"layer_norm.fwd.{dtype.__name__}", out, ref, tol)

        def loss_p(x, w, b):
            return jnp.sum(layer_norm_pallas(x, w, b) *
                           g.astype(jnp.float32))

        def loss_r(x, w, b):
            return jnp.sum(reference_layer_norm(x, w, b) *
                           g.astype(jnp.float32))

        dp = jax.grad(loss_p, (0, 1, 2))(x, w, b)
        dr = jax.grad(loss_r, (0, 1, 2))(x, w, b)
        for nm, a, c in zip(("dx", "dw", "db"), dp, dr):
            check(f"layer_norm.{nm}.{dtype.__name__}", a, c, tol * 4)


def flash():
    from paddle_tpu.ops.flash_attention import (
        flash_attention_bhsd, reference_attention_bhsd)
    # f32 tolerance note: on TPU the MXU computes f32 matmuls with
    # bf16 passes at DEFAULT precision — on BOTH the Pallas kernel and
    # the XLA reference path — so the two f32 pipelines agree only to
    # ~4e-3 relative (measured on v5e, 2026-07-30). bf16 is the
    # training dtype and the tight oracle; f32 here checks plumbing,
    # not accumulation exactness (interpret-mode tests cover that).
    # (tag, dtype, bh, sq, sk, d, causal, q_offset, n_rep, tol, do_bwd)
    # GQA backward is OPT-IN (TPU_PARITY_GQA_BWD=1): its dkv Mosaic
    # compile has never finished on record — give it its own chip call
    # with a timeout (tools/gqa_bwd_repro.py goes smallest-first).
    import os
    gqa_bwd = os.environ.get("TPU_PARITY_GQA_BWD") == "1"
    cases = [
        ("f32.causal", jnp.float32, 8, 512, 512, 128, True, 0, 1, 8e-3,
         True),
        ("bf16.causal", jnp.bfloat16, 8, 512, 512, 128, True, 0, 1,
         2e-2, True),
        ("bf16.full", jnp.bfloat16, 8, 512, 512, 128, False, 0, 1,
         2e-2, True),
        ("bf16.decode", jnp.bfloat16, 8, 128, 512, 128, True, 384, 1,
         2e-2, True),
        ("bf16.gqa4", jnp.bfloat16, 16, 512, 512, 128, True, 0, 4,
         2e-2, gqa_bwd),
    ]
    for tag, dt, bh, sq, sk, d, causal, qoff, n_rep, tol, do_bwd in cases:
        kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(kq, (bh, sq, d), dt)
        k = jax.random.normal(kk, (bh // n_rep, sk, d), dt)
        v = jax.random.normal(kv, (bh // n_rep, sk, d), dt)
        g = jax.random.normal(kg, (bh, sq, d), dt)
        scale = 1.0 / np.sqrt(d)

        def ref(q, k, v):
            if n_rep > 1:
                k2 = jnp.repeat(k, n_rep, axis=0)
                v2 = jnp.repeat(v, n_rep, axis=0)
            else:
                k2, v2 = k, v
            if qoff:
                # bottom-right causal: emulate via full keys and a row offset
                qf = jnp.pad(q, ((0, 0), (qoff, 0), (0, 0)))
                o = reference_attention_bhsd(qf, k2, v2, scale, causal)
                return o[:, qoff:, :]
            return reference_attention_bhsd(q, k2, v2, scale, causal)

        out = flash_attention_bhsd(q, k, v, scale, causal, 128, 128, False,
                                   qoff, n_rep)
        check(f"flash.fwd.{tag}", out, ref(q, k, v), tol)
        if not do_bwd:
            print(json.dumps({"skip": f"flash.bwd.{tag}",
                              "reason": "GQA bwd opt-in only "
                              "(TPU_PARITY_GQA_BWD=1)"}), flush=True)
            continue

        def loss_p(q, k, v):
            o = flash_attention_bhsd(q, k, v, scale, causal, 128, 128,
                                     False, qoff, n_rep)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))

        def loss_r(q, k, v):
            return jnp.sum(ref(q, k, v).astype(jnp.float32)
                           * g.astype(jnp.float32))

        dp = jax.grad(loss_p, (0, 1, 2))(q, k, v)
        dr = jax.grad(loss_r, (0, 1, 2))(q, k, v)
        for nm, a, b in zip(("dq", "dk", "dv"), dp, dr):
            check(f"flash.{nm}.{tag}", a, b, tol * 5)


def rope():
    from paddle_tpu.ops.pallas.rope import rope_bhsd, reference_rope
    for neox in (False, True):
        x = jax.random.normal(jax.random.PRNGKey(5), (8, 256, 128),
                              jnp.bfloat16)
        pos = jnp.arange(256, dtype=jnp.float32)
        inv = 1.0 / (10000.0 ** (jnp.arange(0, 128, 2, dtype=jnp.float32)
                                 / 128.0))
        ang = pos[:, None] * inv[None, :]
        if neox:
            ang = jnp.concatenate([ang, ang], -1)
        else:
            ang = jnp.repeat(ang, 2, -1)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        out = rope_bhsd(x, cos, sin, neox)
        ref = reference_rope(x, cos, sin, neox)
        check(f"rope.fwd.neox={neox}", out, ref, 2e-2)
        g = jax.random.normal(jax.random.PRNGKey(6), x.shape, x.dtype)
        dxp = jax.grad(lambda x: jnp.sum(
            rope_bhsd(x, cos, sin, neox).astype(jnp.float32)
            * g.astype(jnp.float32)))(x)
        dxr = jax.grad(lambda x: jnp.sum(
            reference_rope(x, cos, sin, neox).astype(jnp.float32)
            * g.astype(jnp.float32)))(x)
        check(f"rope.dx.neox={neox}", dxp, dxr, 2e-2)


def adamw():
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    p = jax.random.normal(jax.random.PRNGKey(7), (1000, 257), jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), (1000, 257), jnp.float32)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    lr, b1, b2, eps, wd = 1e-3, 0.9, 0.999, 1e-8, 0.01
    np_, nm, nv = fused_adamw_update(p, g, m, v, lr, b1, b2 ** 1, b1, b2,
                                     eps, wd)
    # unfused reference
    mr = b1 * m + (1 - b1) * g
    vr = b2 * v + (1 - b2) * g * g
    mh = mr / (1 - b1)
    vh = vr / (1 - b2)
    pr = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    check("adamw.m", nm, mr, 1e-6)
    check("adamw.v", nv, vr, 1e-6)
    check("adamw.p", np_, pr, 1e-5)


def softmax_ce():
    from paddle_tpu.ops.pallas.softmax_ce import (softmax_ce_pallas,
                                                  reference_softmax_ce)
    import numpy as np
    rs = np.random.RandomState(0)
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        x = jnp.asarray(rs.randn(256, 50304), dtype)
        lab = jnp.asarray(rs.randint(0, 50304, 256), jnp.int32)
        lab = lab.at[0].set(-100)
        got = softmax_ce_pallas(x, lab)
        want = reference_softmax_ce(x, lab)
        check(f"softmax_ce.fwd.{dtype.__name__}", got, want, tol)

        def lp(x):
            return jnp.sum(softmax_ce_pallas(x, lab))

        def lr(x):
            return jnp.sum(reference_softmax_ce(x, lab))

        check(f"softmax_ce.dx.{dtype.__name__}", jax.grad(lp)(x),
              jax.grad(lr)(x), tol * 4)


def paged():
    """Kernel vs jnp reference for paged decode attention (the kernel
    only exists on TPU — no interpret mode, so hardware is the first
    place the two paths can be compared)."""
    from paddle_tpu.ops.paged_attention import paged_attention_ref
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as _pa)
    import numpy as np
    rs = np.random.RandomState(0)
    nkv, nh, hd, ps, pages = 2, 8, 128, 16, 32
    for dtype, tol in ((jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)):
        q = jnp.asarray(rs.randn(4, nh, hd), dtype)
        kp = jnp.asarray(rs.randn(nkv, pages, ps, hd), dtype)
        vp = jnp.asarray(rs.randn(nkv, pages, ps, hd), dtype)
        lengths = jnp.asarray([5, 40, 63, 64], jnp.int32)
        tables = jnp.asarray(rs.permutation(pages)[:16].reshape(4, 4),
                             jnp.int32)
        scale = 1.0 / np.sqrt(float(hd))
        got = _pa(q * jnp.asarray(scale, dtype), kp, vp, lengths, tables,
                  pages_per_compute_block=4)
        want = paged_attention_ref(q, kp, vp, lengths, tables)
        check(f"paged_attention.{dtype.__name__}", got, want, tol)


def main():
    ds = jax.devices()
    info = {"platform": ds[0].platform,
            "device_kind": getattr(ds[0], "device_kind", "?")}
    INFO.update(info)
    print(json.dumps(info), flush=True)
    if ds[0].platform == "cpu":
        print(json.dumps({"fatal": "no TPU — refusing to run parity on "
                          "CPU (use the interpret-mode tests)"}))
        return 1
    run("rms_norm", rms_norm)
    run("layer_norm", layer_norm)
    run("softmax_ce", softmax_ce)
    run("rope", rope)
    run("adamw", adamw)
    run("flash_attention", flash)
    run("paged_attention", paged)
    n_ok = sum(1 for r in RESULTS if r.get("ok"))
    summary = {"summary": True, "ok": n_ok, "total": len(RESULTS),
               "all_ok": n_ok == len(RESULTS), **info}
    print(json.dumps(summary), flush=True)
    _persist(complete=True)
    return 0 if n_ok == len(RESULTS) else 2


if __name__ == "__main__":
    sys.exit(main())
