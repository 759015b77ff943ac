"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

Drives the two paths a user pays for, once each, through their normal
entry points at the published widths of ``gpt3-760M`` (hidden 1536, 16
heads of 96, 24 layers, vocab 50304, seq 2048; weights random from a
seed):

* **train** — ``amp.decorate(O2, bf16)`` + ``AdamW(multi_precision)`` +
  ``jit.train_step``, a few steps on one repeated seeded batch;
* **serve** — ``ServingEngine`` behind ``InferenceServer``, concurrent
  greedy requests over ``POST /generate``.

Before each path, every Pallas kernel it reaches is compared once with
its in-repo jnp reference at the path's own shapes.  Any failed check or
exception fails the run; nothing carries on past a failed phase, and
there is no route to a CPU result: without an accelerator the script
exits non-zero and prints no result line.

A chip belongs to one process at a time, and the trainer's 10 GB of
optimizer state cannot share 16 GB with the server's weights and page
pools.  So this parent process never imports jax: it runs the phases as
children, one after the other, and each child holds the chip alone.  The
children share the persistent compile cache that ``import paddle_tpu``
places (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).

    python chip_smoke.py               # one chip: train, then serve
    python chip_smoke.py --four-chip   # the GSPMD train step on 4 chips
    python chip_smoke.py --rehearse    # CPU rehearsal, tiny preset,
                                       # Pallas interpret mode; for
                                       # debugging the script itself

The last stdout line is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it.  The line before it, ``report: {...}``,
holds what each phase measured; the seconds in it are a record of one
run, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import threading
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

# one phase may take this long before the parent kills it (the driver
# allows the whole script 1200 s, compilation included)
PHASE_TIMEOUT_S = 1100


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def _sizes(rehearse: bool) -> dict:
    if rehearse:
        return dict(preset="tiny", seq=128, batch=2, steps=5, max_batch=8,
                    new_tokens=8, prompts=(90, 40, 20, 6), prefix=16,
                    four_chip_batch=4, four_chip_steps=3)
    # batch 4: the compiler's memory analysis of this step for a v5e
    # puts batch 8 past 16 GB (10 GB of state plus 13 GB of temporaries)
    # and batch 4 inside it.  Four chips, batch 2: under the 2x2 mesh
    # the same analysis gives 5 GB of state a chip and temporaries that
    # grow from 4.6 GB at batch 2 to 11.5 GB at batch 4.
    return dict(preset="gpt3-760M", seq=2048, batch=4, steps=5, max_batch=8,
                new_tokens=32, prompts=(1500, 700, 200, 24), prefix=64,
                four_chip_batch=2, four_chip_steps=3)


# ---------------------------------------------------------------------------
# shared child helpers (everything below imports jax: children only)
# ---------------------------------------------------------------------------

def _check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def _device(rehearse: bool, need: int = 1) -> dict:
    """The device as JAX reports it; the wrong platform ends the run."""
    from importlib import metadata
    import jax
    import jaxlib
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise SystemExit(f"chip_smoke: needs platform {want!r}, jax "
                         f"reports {devs[0].platform!r}")
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: needs {need} devices, jax reports "
                         f"{len(devs)}")
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"platform: {dev['platform']}  device_kind: {dev['kind']}  "
          f"devices: {dev['count']}  jax {jax.__version__}  "
          f"jaxlib {jaxlib.__version__}  "
          f"libtpu {metadata.version('libtpu')}", flush=True)
    return dev


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _max_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def _check_kernels(jitted, args, expected, rehearse: bool, what: str):
    """Every name in ``expected`` is a Mosaic kernel of the lowered
    program (a silently taken reference route leaves its kernel out).
    In the interpret-mode rehearsal kernels lower to plain HLO, so only
    the number of ``pallas_call`` equations is known."""
    import collections
    import re
    traced = jitted.trace(*args)
    if rehearse:
        found = {"pallas_call": str(traced.jaxpr).count("pallas_call")}
        expected = ("pallas_call",)
    else:
        text = traced.lower().as_text()
        found = dict(collections.Counter(
            re.findall(r'kernel_name\s*=\s*"([^"]+)"', text)))
        _check(sum(found.values()) == text.count("tpu_custom_call"),
               f"every tpu_custom_call in the lowered {what} carries a "
               f"kernel name")
    print(f"  kernels in the lowered {what}: {found}", flush=True)
    for name in expected:
        _check(found.get(name, 0) > 0, f"{what} contains {name}")


# ---------------------------------------------------------------------------
# kernels the trainer reaches, against their references
# ---------------------------------------------------------------------------

def _train_kernel_parity(cfg, seq: int, rehearse: bool) -> None:
    """flash fwd+bwd, layer-norm fwd+bwd and fused AdamW at the train
    step's shapes.  References run at ``highest`` matmul precision (a
    float32 matmul on the MXU is otherwise a single bf16 pass)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.flags import get_flag
    from paddle_tpu.ops.flash_attention import (flash_attention_bhsd,
                                                reference_attention_bhsd)
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    from paddle_tpu.ops.pallas.layer_norm import (layer_norm_pallas,
                                                  reference_layer_norm)
    interp = bool(get_flag("pallas_interpret"))
    nh, hidden = cfg.num_heads, cfg.hidden_size
    hd = hidden // nh
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    print("kernel parity (train shapes):", flush=True)

    # --- flash attention: one batch row of heads, [nh, seq, hd] bf16.
    # Tolerance 2e-2 of the largest reference value: outputs and
    # gradients are rounded to bf16 (2^-8 relative) on both sides and
    # the kernel's float32 dots run as bf16 MXU passes.
    q, k, v, g = (jax.random.normal(kk, (nh, seq, hd), jnp.bfloat16)
                  for kk in ks[:4])
    scale = 1.0 / math.sqrt(hd)
    blk = min(128, seq)

    def flash(q, k, v):
        return flash_attention_bhsd(q, k, v, scale, True, blk, blk,
                                    interp, 0, 1)

    def ref(q, k, v):
        return reference_attention_bhsd(q, k, v, scale, True)

    out, vjp = jax.vjp(flash, q, k, v)
    with jax.default_matmul_precision("highest"):
        rout, rvjp = jax.vjp(ref, q, k, v)
        want = (rout,) + rvjp(g)
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out,) + vjp(g), want):
        e = _max_err(a, b)
        _check(e < 2e-2, f"flash {name} vs reference_attention_bhsd: "
                         f"max err {e:.2e} < 2e-2")

    # --- layer norm: [rows, hidden] bf16 activations, float32 weights
    # (AMP O2 keeps norm layers float32).  Tolerance 1e-2: one bf16
    # rounding of the output; no matmul is involved.
    rows = 4 * seq
    x = jax.random.normal(ks[4], (rows, hidden), jnp.bfloat16)
    w = 1.0 + 0.1 * jax.random.normal(ks[5], (hidden,), jnp.float32)
    b = 0.1 * jax.random.normal(ks[6], (hidden,), jnp.float32)
    gy = jax.random.normal(ks[7], (rows, hidden), jnp.bfloat16)
    out, vjp = jax.vjp(lambda x, w, b: layer_norm_pallas(
        x, w, b, 1e-5, 256, interp), x, w, b)
    rout, rvjp = jax.vjp(lambda x, w, b: reference_layer_norm(
        x, w, b, 1e-5), x, w, b)
    for name, a, b_ in zip(("out", "dx", "dw", "db"), (out,) + vjp(gy),
                           (rout,) + rvjp(gy)):
        e = _max_err(a, b_)
        _check(e < 1e-2, f"layer_norm {name} vs reference_layer_norm: "
                         f"max err {e:.2e} < 1e-2")

    # --- fused AdamW on a float32 master weight of the qkv projection's
    # shape against the unfused sequence (optimizer/optimizers.py).
    # Tolerance 1e-5: the same float32 elementwise arithmetic, differing
    # only in the rounding of sqrt and the divisions.
    shape = (hidden, 3 * hidden)
    p = jax.random.normal(ks[0], shape, jnp.float32)
    gr = jax.random.normal(ks[1], shape, jnp.float32)
    m = 0.1 * jax.random.normal(ks[2], shape, jnp.float32)
    vv = jnp.square(jax.random.normal(ks[3], shape, jnp.float32))
    lr, b1, b2, eps, b1p, b2p = 1e-4, 0.9, 0.999, 1e-8, 0.9 ** 3, 0.999 ** 3
    got = fused_adamw_update(p, gr, m, vv, lr, b1p, b2p, b1, b2, eps)
    m2 = b1 * m + (1 - b1) * gr
    v2 = b2 * vv + (1 - b2) * jnp.square(gr)
    p2 = p - lr * (m2 / (1 - b1p)) / (jnp.sqrt(v2 / (1 - b2p)) + eps)
    for name, a, b_ in zip(("p", "m", "v"), got, (p2, m2, v2)):
        e = _max_err(a, b_)
        _check(e < 1e-5, f"fused_adamw {name} vs the unfused sequence: "
                         f"max err {e:.2e} < 1e-5")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def _build_model(sz: dict, sequence_parallel: bool = False):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForPretraining, gpt_config
    paddle.seed(0)
    cfg = gpt_config(sz["preset"], max_position_embeddings=sz["seq"],
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     use_recompute=True,
                     sequence_parallel=sequence_parallel)
    return cfg, GPTForPretraining(cfg)


def _train_step(model, wrap_optimizer=lambda o: o):
    """Optimizer, AMP and the jitted step as ``benchmark/runners/
    train.py`` builds them (``wrap_optimizer``: fleet's wrapper on the
    four-chip leg)."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.jit import train_step
    optimizer = wrap_optimizer(opt.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        weight_decay=0.01, multi_precision=True))
    model, optimizer = amp.decorate(models=model, optimizers=optimizer,
                                    level="O2", dtype="bfloat16")

    def step_fn(m, ids, labels):
        # O2 is pure-half: the auto_cast hook must be live during the
        # trace, decorate() alone only casts parameters
        with amp.auto_cast(enable=True, level="O2", dtype="bfloat16"):
            return m.loss_fn(m(ids), labels)

    return train_step(model, None, optimizer, step_fn=step_fn)


def _run_steps(step, cfg, batch: int, seq: int, steps: int):
    """``steps`` steps on one repeated seeded batch; returns the losses
    and each step's wall seconds (every step ends in a host read of the
    loss, so the clock covers the device work)."""
    import numpy as np
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    losses, secs = [], []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = float(step(ids, labels))
        secs.append(round(time.perf_counter() - t0, 3))
        losses.append(loss)
        print(f"  step {i}: loss {loss:.4f}  {secs[-1]:.3f} s", flush=True)
    return losses, secs


def _check_losses(losses, vocab: int) -> None:
    _check(all(math.isfinite(x) for x in losses), "every loss is finite")
    _check(abs(losses[0] - math.log(vocab)) < 0.5,
           f"first loss {losses[0]:.3f} within 0.5 of ln({vocab}) = "
           f"{math.log(vocab):.3f}")
    _check(losses[-1] < losses[0],
           f"last loss {losses[-1]:.4f} below the first {losses[0]:.4f}")


def phase_train(rehearse: bool) -> dict:
    sz = _sizes(rehearse)
    dev = _device(rehearse)
    cfg, model = _build_model(sz)
    _train_kernel_parity(cfg, sz["seq"], rehearse)
    step = _train_step(model)
    print(f"train: {sz['preset']} layers={cfg.num_layers} "
          f"hidden={cfg.hidden_size} heads={cfg.num_heads} "
          f"vocab={cfg.vocab_size} seq={sz['seq']} batch={sz['batch']} "
          f"AMP O2 bf16, AdamW multi_precision, recompute on", flush=True)
    losses, secs = _run_steps(step, cfg, sz["batch"], sz["seq"],
                              sz["steps"])
    _check_losses(losses, cfg.vocab_size)
    # TrainStep traces twice: step 0 creates the optimizer state inside
    # the trace (bootstrap), step 1 takes it as input (steady)
    _check_kernels(
        step._jitted, step._cost_args,
        ("_adamw_kernel", "_fwd_kernel", "_bwd_dq_kernel",
         "_bwd_dkv_kernel", "_ln_fwd_kernel", "_ln_bwd_kernel"),
        rehearse, "steady train step")
    report = {"phase": "train", "device": dev, "preset": sz["preset"],
              "batch": sz["batch"], "seq": sz["seq"], "losses": losses,
              "bootstrap_compile_and_step_s": secs[0],
              "steady_compile_and_step_s": secs[1],
              "steady_step_s": sorted(secs[2:])[len(secs[2:]) // 2],
              "peak_bytes_in_use": _peak_bytes()}
    print(f"train: bootstrap trace+compile+step {secs[0]} s, steady "
          f"trace+compile+step {secs[1]} s, steady step "
          f"{report['steady_step_s']} s, peak_bytes_in_use "
          f"{report['peak_bytes_in_use']}", flush=True)
    return report


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _ragged_parity(cfg, sz: dict, page_size: int) -> None:
    """The ragged kernel at Q=1 (every decode-only step) and at the
    widest prefill bucket, in the engine's pool geometry and dtype,
    against ``ragged_paged_attention_ref`` at ``highest`` precision.
    Tolerance 2e-2 of the largest reference value: the kernel's float32
    dots run as bf16 MXU passes (2^-8 relative per product)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    _check(rpa.available(), "the ragged Pallas kernel is the route here")
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    b, ps = sz["max_batch"], page_size
    max_pos = cfg.max_position_embeddings
    ppseq = -(-max_pos // ps)
    npages = b * ppseq + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    kp = jax.random.normal(ks[0], (nh, npages, ps, hd), jnp.float32)
    vp = jax.random.normal(ks[1], (nh, npages, ps, hd), jnp.float32)
    rs = np.random.RandomState(1)
    tables = rs.permutation(npages - 1)[:b * ppseq] \
        .reshape(b, ppseq).astype("int32")
    wide = 1
    while wide < max(sz["prompts"]):
        wide <<= 1
    print(f"kernel parity (ragged, pools [{nh}, {npages}, {ps}, {hd}] "
          f"float32):", flush=True)
    for qw in (1, wide):
        q = jax.random.normal(ks[2], (b, qw, nh, hd), jnp.float32)
        if qw == 1:      # pure decode: one token per lane, one lane empty
            kv_lens = rs.randint(1, max_pos + 1, (b,)).astype("int32")
            q_lens = np.ones((b,), "int32")
            kv_lens[-1] = q_lens[-1] = 0
        else:            # the smoke's own mix: prefills beside a decode
            q_lens = np.zeros((b,), "int32")
            kv_lens = np.zeros((b,), "int32")
            for i, n in enumerate(sz["prompts"]):
                q_lens[i] = kv_lens[i] = n
            q_lens[len(sz["prompts"])] = 1           # a decoding lane
            kv_lens[len(sz["prompts"])] = max_pos // 2
        got = np.asarray(jax.jit(rpa.ragged_paged_attention)(
            q, kp, vp, kv_lens, q_lens, tables))
        _check(bool(np.all(np.isfinite(got))),
               f"ragged Q={qw}: every output row is finite")
        worst = 0.0
        with jax.default_matmul_precision("highest"):
            for i in range(b):       # lane by lane: the dense reference
                n = int(q_lens[i])   # holds [nh, Q, T] logits per lane
                if not n:
                    continue
                want = rpa.ragged_paged_attention_ref(
                    q[i:i + 1, :n], kp, vp, kv_lens[i:i + 1],
                    q_lens[i:i + 1], tables[i:i + 1])
                worst = max(worst, _max_err(got[i, :n], want[0]))
        _check(worst < 2e-2, f"ragged Q={qw} vs ragged_paged_attention_"
                             f"ref: max err {worst:.2e} < 2e-2")


def _engine_program_args(engine, qw: int, sharding=None):
    """Abstract arguments of the engine's jitted ragged program at chunk
    width ``qw`` (the shapes ``_dispatch_step`` feeds it: the plan's
    packed token rows, the unread step's sampled row and the rows that
    take a token from it), on the attached device or on the described one
    that ``sharding`` names."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.scheduler import step_rows
    b = engine.max_batch
    rows = step_rows(qw, b)
    ppseq = engine.scheduler.ppseq

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    like = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)
    return (like(engine._params), sds((rows,), jnp.int64),
            sds((rows,), jnp.int32), like(engine._pools),
            sds((rows,), jnp.int32), sds((rows,), jnp.int32),
            sds((b,), jnp.int32), sds((b,), jnp.int32),
            sds((b, ppseq), jnp.int32), sds((b,), jnp.float32),
            like(engine._key), sds((b,), jnp.float32),
            like(engine._no_prev), sds((rows,), jnp.int32))


# the batch cell's attention geometry (mistral-7b-8l: 8 lanes, 32 heads
# over 8 kv heads of 128, 2049 pages of 16, 256 of them a sequence).  A
# head of 128 is a whole lane tile, so the compiler keeps such a pool
# row-major and the k/v write has to leave no copy of it
_HD128_LAYER = dict(b=8, nh=32, nkv=8, hd=128, pages=2049, ps=16, ppseq=256)
# the third configuration's two attention geometries (mimo-v2.5-7l-ep32:
# 64 query heads, keys of 192 and values of 128).  A full layer: 4 kv
# heads over the shared pool of 4097 pages, 512 a sequence.  A window
# layer: 8 kv heads, a window of 128 with a sink a head, and a ring of
# 73 pages a lane (8 x 73 + the sink).  Keys of 192 are a tile and a
# half, and a pool that wide is re-laid twice a step around the Mosaic
# call (hd=192 here shows it: 2 copies of the key pool at Q=1 and at
# Q=1024), so the program pads its key pools to 256
# (generation._pool_width), which is the geometry guarded here
_KEY192_FULL_LAYER = dict(b=8, nh=64, nkv=4, hd=256, hdv=128, pages=4097,
                          ps=16, ppseq=512)
_KEY192_WINDOW_LAYER = dict(b=8, nh=64, nkv=8, hd=256, hdv=128, pages=585,
                            ps=16, ppseq=73, window=128, sink=True)
# the sixth configuration's attention layer (lfm2-24b-a2b-5l: 8 lanes, 32
# heads over 8 kv heads of 64, 5121 pages of 16, 1024 a sequence).  A
# pool 64 wide is half a lane tile: the k/v write's flat view of it is a
# copy, not a bitcast, and the launch pads it to 128 (hd=64 here shows
# it), so the program pads heads of 64 to 128 (generation._pool_width),
# which is the geometry guarded here
_HD64_LAYER = dict(b=8, nh=32, nkv=8, hd=128, pages=5121, ps=16, ppseq=1024)


def _pool_copies(text: str, pool) -> int:
    """``copy`` instructions of a compiled program that produce a whole
    page pool: a layout change around the k/v write or the Mosaic call,
    the pool read and written once each."""
    import re
    dt = {"float32": "f32", "bfloat16": "bf16"}[str(pool.dtype)]
    shape = f"{dt}[{','.join(str(n) for n in pool.shape)}]"
    return len(re.findall(rf"= {re.escape(shape)}\S* copy\(", text))


def _compile_serve_layer(qw: int, b, nh, nkv, hd, pages, ps, ppseq,
                         sharding=None, hdv=None, window=None, sink=False,
                         rows=None):
    """One layer's attention of the ragged step (the k/v write into both
    donated pools, then the ragged kernel) compiled at chunk width
    ``qw``, for the attached device or for the described one that
    ``sharding`` names.  ``hdv``: values narrower than keys; ``window``,
    ``sink``: a window layer's kernel over a ring of ``ppseq`` pages a
    lane.  ``rows``: the layer as the engine's step runs it, over that
    many packed rows (``step_rows(qw, b)``) and not ``[b, qw]``.
    Returns ``(compiled, pool)``, the key pool as its abstract shape."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.generation import _scatter_pages
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        _ragged_pallas, _ragged_pallas_rows)
    hdv = hd if hdv is None else hdv
    lead = (b, qw) if rows is None else (rows,)

    def layer(pools, q, k, v, page_ids, slots, kv_lens, q_lens, tables,
              sinks):
        kp = _scatter_pages(pools[0], k, page_ids, slots)
        vp = _scatter_pages(pools[1], v, page_ids, slots)
        rest = (1.0 / math.sqrt(hd), window, sinks if sink else None)
        if rows is None:
            return _ragged_pallas(q, kp, vp, kv_lens, q_lens, tables,
                                  *rest), (kp, vp)
        offs = jnp.cumsum(q_lens) - q_lens
        return _ragged_pallas_rows(q, kp, vp, kv_lens, q_lens, offs, tables,
                                   qw, *rest), (kp, vp)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = sds((nkv, pages, ps, hd), jnp.float32)
    compiled = jax.jit(layer, donate_argnums=(0,)).lower(
        (pool, sds((nkv, pages, ps, hdv), jnp.float32)),
        sds((*lead, nh, hd), jnp.float32),
        sds((*lead, nkv, hd), jnp.float32),
        sds((*lead, nkv, hdv), jnp.float32), sds(lead, jnp.int32),
        sds(lead, jnp.int32), sds((b,), jnp.int32),
        sds((b,), jnp.int32), sds((b, ppseq), jnp.int32),
        sds((nh,), jnp.float32)).compile()
    return compiled, pool


# one layer of the batch cell's model (mistral-7b-8l's widths) behind a
# small vocabulary, as the engine serves it: 8 lanes, 2049 pages of 16
_MISTRAL_LAYER_MODEL = dict(vocab_size=2048, hidden_size=4096, num_layers=1,
                            num_heads=32, num_kv_heads=8,
                            intermediate_size=14336,
                            max_position_embeddings=4096, rms_eps=1e-5,
                            rope_theta=1e6)


def _matmul_rows(text: str) -> list:
    """The row counts (first dimension of the 2-D output) of a compiled
    program's matrix products: ``convolution`` on a TPU, ``dot`` on the
    CPU."""
    import re
    return sorted({int(m) for m in re.findall(
        r"= \w+\[(\d+),\d+\]\S* (?:convolution|dot)\(", text)})


def _compile_packed_step(qw: int, sharding=None, rehearse: bool = False):
    """The engine's OWN program at chunk width ``qw`` for one layer at
    the batch cell's widths (``serve_step_q<qw>``: the plan's packed
    rows through ``step.packed``, pools donated), compiled for the
    attached device or for the described one that ``sharding`` names.
    Returns ``(compiled, key pool, rows)``."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.scheduler import step_rows
    widths = dict(_MISTRAL_LAYER_MODEL)
    pages = 2049
    if rehearse:
        widths.update(vocab_size=128, hidden_size=64, num_heads=4,
                      num_kv_heads=2, intermediate_size=128,
                      max_position_embeddings=256)
        pages = 33
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(**widths))
    engine = ServingEngine(model, max_batch=8, page_size=16,
                           num_pages=pages)
    compiled = engine._program(qw).lower(
        *_engine_program_args(engine, qw, sharding)).compile()
    return compiled, engine._pools[0][0], step_rows(qw, engine.max_batch)


def _check_packed_rows(rehearse: bool) -> dict:
    """The widest prefill program of one batch-cell layer runs its
    matrix products over the packed rows (``step_rows``: the chunk and a
    token a lane), none over ``max_batch x q_width``, and copies no
    pool.  The CPU rehearsal compiles a tiny layer at Q=64 to exercise
    the code."""
    qw = 64 if rehearse else 1024
    compiled, pool, rows = _compile_packed_step(qw, rehearse=rehearse)
    text = compiled.as_text()
    products = _matmul_rows(text)
    copies = _pool_copies(text, pool)
    print(f"  serve_step_q{qw} of one batch-cell layer: q_width {qw}, rows "
          f"{rows} (8 lanes x {qw} = {8 * qw} padded), matrix products "
          f"over {products} rows, {copies} whole-pool copies", flush=True)
    _check(rows in products and 8 * qw not in products,
           f"the Q={qw} program multiplies {rows} packed rows and nothing "
           f"at {8 * qw}")
    if not rehearse:
        _check(copies == 0, f"the Q={qw} program copies no page pool")
    return {"packed_q_width": qw, "packed_rows": rows,
            "packed_matmul_rows": products, "pool_copies_packed": copies}


def _check_pool_copies(engine, rehearse: bool) -> dict:
    """Whole-pool copies in the compiled decode-only step.  There must
    be none: the k/v write is a row scatter into the pool seen as
    ``[nkv * P * ps, hd]``, whose layout is the Mosaic call's, and every
    pool is a whole number of lane tiles wide (the smoke's own heads of
    96, the batch cell's of 128, the sixth configuration's of 64 in
    pools of 128).  The CPU backend donates nothing and has layouts of
    its own, so the rehearsal only exercises the code."""
    own = engine._program(1).lower(
        *_engine_program_args(engine, 1)).compile()
    n_own = _pool_copies(own.as_text(), engine._pools[0][0])
    narrow = {}
    for name, geometry in (("hd128", _HD128_LAYER), ("hd64", _HD64_LAYER)):
        geometry = dict(geometry)
        if rehearse:
            geometry.update(pages=33, ppseq=4)
        layer, pool = _compile_serve_layer(1, **geometry)
        narrow[name] = _pool_copies(layer.as_text(), pool)
    print(f"  whole-pool copies in the compiled Q=1 programs: "
          f"{n_own} in the engine's step (pools "
          f"{list(engine._pools[0][0].shape)}), {narrow['hd128']} in one "
          f"layer at the batch cell's geometry, {narrow['hd64']} in one at "
          f"heads of 64 in pools of 128", flush=True)
    # the third configuration's two kinds of layer: keys of 192 in pools
    # padded to 256, values of 128; a ring, a window and sinks in one
    wide = {}
    for kind, geo in (("full", _KEY192_FULL_LAYER),
                      ("window", _KEY192_WINDOW_LAYER)):
        geo = dict(geo)
        if rehearse:
            geo.update(pages=geo["b"] * 3 + 1, ppseq=3)
        layer, pool = _compile_serve_layer(1, **geo)
        wide[kind] = _pool_copies(layer.as_text(), pool)
    print(f"  and in one full and one window layer at keys of 192 "
          f"(pools 256 wide): {wide}", flush=True)
    if not rehearse:
        _check(n_own == 0, "the engine's own step at heads of 96 in pools "
                           "of 128 compiles with no copy of a page pool")
        _check(narrow["hd128"] == 0,
               "the k/v write and the ragged kernel at heads of 128 "
               "compile with no copy of a page pool")
        _check(narrow["hd64"] == 0,
               "a layer at heads of 64 in pools of 128 compiles with no "
               "copy of a page pool")
        _check(not any(wide.values()),
               "a full and a window layer at keys of 192 padded to 256 "
               "compile with no copy of a key pool")
    return {"pool_copies_q1": n_own,
            "pool_copies_q1_hd128_layer": narrow["hd128"],
            "pool_copies_q1_hd64_layer": narrow["hd64"],
            "pool_copies_q1_key192_full_layer": wide["full"],
            "pool_copies_q1_key192_window_layer": wide["window"]}


def phase_serve(rehearse: bool) -> dict:
    import numpy as np
    sz = _sizes(rehearse)
    dev = _device(rehearse)
    import paddle_tpu as paddle
    from paddle_tpu.flags import set_flags
    from paddle_tpu.inference.serving import InferenceServer, generate_http
    from paddle_tpu.models import GPTForPretraining, gpt_config
    from paddle_tpu.serving import ServingEngine
    page_size = 16
    cfg = gpt_config(sz["preset"], max_position_embeddings=sz["seq"],
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    _ragged_parity(cfg, sz, page_size)

    paddle.seed(0)
    model = GPTForPretraining(cfg)
    model.eval()
    rs = np.random.RandomState(0)
    shared = rs.randint(0, cfg.vocab_size, (sz["prefix"],)).tolist()

    def make_prompts():
        """Fresh random prompts of the smoke's lengths, longest first,
        plus two made of the shared prefix and one own token: sent
        together they both miss the prefix cache; sent again in a later
        wave they hit it and feed one token, which makes no new Q
        bucket."""
        return [rs.randint(0, cfg.vocab_size, (n,)).tolist()
                for n in sz["prompts"]] + \
            [shared + rs.randint(0, cfg.vocab_size, (1,)).tolist()
             for _ in range(2)]

    n_new = sz["new_tokens"]
    print(f"serve: {sz['preset']} float32, max_batch={sz['max_batch']} "
          f"page_size={page_size}; each wave is "
          f"{len(sz['prompts']) + 2} concurrent greedy requests over "
          f"POST /generate, prompts {list(sz['prompts'])} tokens and two "
          f"of a shared {sz['prefix']}-token prefix plus one token, "
          f"{n_new} new tokens each", flush=True)

    set_flags({"FLAGS_serving_engine": True})
    engine = ServingEngine(model, max_batch=sz["max_batch"],
                           page_size=page_size)
    results: dict = {}

    def one(i, ids):
        t0 = time.perf_counter()
        try:
            toks = list(generate_http(srv.url, ids, max_new_tokens=n_new,
                                      timeout=PHASE_TIMEOUT_S))
        except Exception as e:  # noqa: BLE001 — reported and re-raised
            results[i] = e      # by the main thread below
            return
        results[i] = (toks, round(time.perf_counter() - t0, 3))

    def wave(label):
        prompts = make_prompts()
        results.clear()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=one, args=(i, p))
                   for i, p in enumerate(prompts)]
        # the longest prompt gets a head start, so that both waves plan
        # the same steps (it alone, then the rest beside its decode) and
        # the second wave finds every Q bucket it needs compiled
        for t in threads:
            t.start()
            if t is threads[0]:
                time.sleep(0.05)
        for t in threads:
            t.join()
        wall = round(time.perf_counter() - t0, 3)
        for i in range(len(prompts)):
            if isinstance(results[i], Exception):
                raise SystemExit(f"chip_smoke: request {i} failed: "
                                 f"{results[i]!r}")
            toks, secs = results[i]
            print(f"  {label} request {i}: prompt[{len(prompts[i])}] -> "
                  f"{len(toks)} tokens in {secs} s", flush=True)
            _check(len(toks) == n_new
                   and all(0 <= t < cfg.vocab_size for t in toks),
                   f"request {i} returned exactly {n_new} ids in "
                   f"[0, {cfg.vocab_size}), none the -1 NaN sentinel")
        return wall, [results[i][1] for i in range(len(prompts))]

    with engine:
        srv = InferenceServer(engine=engine,
                              stream_timeout=PHASE_TIMEOUT_S).start()
        try:
            # the first wave pays every compile; the second should find
            # its programs compiled (the bucket lists below say whether
            # it did) and its two shared-prefix requests cached
            cold_wall, _ = wave("cold")
            cold_programs = sorted(k[0] for k in engine._programs)
            warm_wall, warm_secs = wave("warm")
        finally:
            srv.stop()
        stats = engine.stats()
        print(f"  engine stats: {stats}", flush=True)
        buckets = sorted(k[0] for k in engine._programs)
        print(f"  Q buckets compiled: cold wave {cold_programs}, after "
              f"the warm wave {buckets}", flush=True)
        _check(stats["health"] == "ok" and stats["quarantined"] == 0
               and stats["cancelled"] == 0
               and stats["watchdog_relaunches"] == 0,
               "engine health ok; nothing quarantined, cancelled or "
               "relaunched")
        _check(stats["prefix_cache"]["hits"] >= 2,
               "the warm wave's shared-prefix requests hit the prefix "
               "cache")
        _check(1 in buckets, "the decode-only (Q=1) program ran")
        _check(max(buckets) >= max(sz["prompts"]),
               f"a prefill program as wide as the longest prompt ran "
               f"(Q={max(buckets)})")
        _check_kernels(engine._program(1), _engine_program_args(engine, 1),
                       ("_ragged_kernel",), rehearse, "ragged step (Q=1)")
        pool_copies = _check_pool_copies(engine, rehearse)
    pool_copies.update(_check_packed_rows(rehearse))
    report = {"phase": "serve", "device": dev, "preset": sz["preset"],
              "requests_per_wave": len(sz["prompts"]) + 2,
              "new_tokens": n_new,
              "q_buckets": buckets, "cold_wave_s": cold_wall,
              "warm_wave_s": warm_wall, "warm_request_s": warm_secs,
              "peak_bytes_in_use": _peak_bytes(), **pool_copies}
    print(f"serve: cold wave (compiles included) {cold_wall} s, warm wave "
          f"{warm_wall} s, peak_bytes_in_use "
          f"{report['peak_bytes_in_use']}", flush=True)
    return report


# ---------------------------------------------------------------------------
# phase: four chips (GSPMD train step under fleet.init)
# ---------------------------------------------------------------------------

def phase_four_chip(rehearse: bool) -> dict:
    import jax
    sz = _sizes(rehearse)
    dev = _device(rehearse, need=4)
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "sharding_degree": 2,
                               "mp_degree": 2, "pp_degree": 1,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    cfg, model = _build_model(sz, sequence_parallel=True)
    model = fleet.distributed_model(model)
    inner = model._layers if hasattr(model, "_layers") else model
    step = _train_step(inner, fleet.distributed_optimizer)
    batch = sz["four_chip_batch"]
    print(f"four-chip train: {sz['preset']} seq={sz['seq']} "
          f"batch={batch} on mesh {dict(step.mesh.shape)} "
          f"(sharding 2 x mp 2, sequence parallel on), AMP O2 bf16",
          flush=True)
    losses, secs = _run_steps(step, cfg, batch, sz["seq"],
                              sz["four_chip_steps"])
    _check_losses(losses, cfg.vocab_size)
    # the state is spread over the mesh, not parked on chip 0 (whose
    # peak also holds the parameters as they were born, before the
    # first step placed them)
    stats = [d.memory_stats() or {} for d in jax.devices()[:4]]
    in_use = [st.get("bytes_in_use") for st in stats]
    print(f"  bytes_in_use per device: {in_use}; peak_bytes_in_use: "
          f"{[st.get('peak_bytes_in_use') for st in stats]}", flush=True)
    if not rehearse:
        _check(min(in_use) > 0.5 * max(in_use),
               "every chip holds a comparable share of the state")
    # an mp-annotated weight with its moment, and the moment of an
    # un-annotated one (ZeRO splits those over the sharding axis)
    moments = step.optimizer._accumulators["moment1"]
    for what, p in (("qkv_proj.weight",
                     inner.gpt.layers[0].attn.qkv_proj.weight),
                    ("position_embeddings.weight",
                     inner.gpt.embeddings.position_embeddings.weight)):
        m = next(v for v in moments.values()
                 if v.shape == p._data.shape)
        print(f"  {what} {p._data.shape}: {p._data.sharding.spec}; its "
              f"AdamW moment1: {m.sharding.spec} on mesh "
              f"{dict(m.sharding.mesh.shape)}", flush=True)
        _check(len(m.sharding.device_set) == 4
               and not m.sharding.is_fully_replicated,
               f"the moment of {what} is split over the 4-device mesh")
    # under the GSPMD mesh flash attention wraps itself in shard_map;
    # layer norm and AdamW take the XLA composition
    _check_kernels(step._jitted, step._cost_args,
                   ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"),
                   rehearse, "four-chip train step")
    return {"phase": "four_chip", "device": dev, "preset": sz["preset"],
            "batch": batch, "losses": losses, "step_s": secs,
            "bytes_in_use": in_use}


PHASES = {"train": phase_train, "serve": phase_serve,
          "four_chip": phase_four_chip}


# ---------------------------------------------------------------------------
# parent: runs the phases as children, one at a time; never imports jax
# ---------------------------------------------------------------------------

def _run_child(phase: str, rehearse: bool) -> dict:
    """Run one phase in its own process, echoing its output; return the
    report it prints as its last line.  A failed child ends the run."""
    env = dict(os.environ)
    if rehearse:
        env.update(JAX_PLATFORMS="cpu", FLAGS_pallas_interpret="1")
        if phase == "four_chip":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count"
                                "=4").strip()
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearse:
        cmd.append("--rehearse")
    print(f"=== phase {phase} ===", flush=True)
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=env, cwd=_ROOT)
    killer = threading.Timer(PHASE_TIMEOUT_S, proc.kill)
    killer.start()
    last = ""
    try:
        for line in proc.stdout:
            if line.strip():
                last = line
            if not line.startswith("{\"phase\""):
                print(line, end="", flush=True)
        rc = proc.wait()
    finally:
        killer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise SystemExit(f"chip_smoke: phase {phase} failed (exit code "
                         f"{rc})")
    report = json.loads(last)
    report["phase_wall_s"] = round(time.perf_counter() - t0, 1)
    print(f"=== phase {phase} passed in {report['phase_wall_s']} s ===",
          flush=True)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run the GSPMD train step on four chips instead "
                         "of the one-chip train and serve phases")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal of the script itself: tiny "
                         "preset, Pallas interpret mode, prints "
                         "platform: cpu")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)     # the children's entry
    args = ap.parse_args(argv)
    if args.phase:
        sys.path.insert(0, _ROOT)
        print(json.dumps(PHASES[args.phase](args.rehearse)), flush=True)
        return 0
    phases = ["four_chip"] if args.four_chip else ["train", "serve"]
    reports = [_run_child(p, args.rehearse) for p in phases]
    device = reports[0]["device"]
    if any(r["device"] != device for r in reports):
        raise SystemExit("chip_smoke: the phases saw different devices")
    detail = {"rehearsal": args.rehearse,
              "phases": {r["phase"]: {k: v for k, v in r.items()
                                      if k not in ("phase", "device")}
                         for r in reports}}
    print("report: " + json.dumps(detail), flush=True)
    # the result line: exactly these two keys, nothing after it
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
