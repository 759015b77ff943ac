"""Benchmark: GPT pretrain tokens/sec/chip (BASELINE.md north star).

Runs in this process on the attached TPU and prints ONE JSON line
{"metric", "value", "unit", "vs_baseline", "platform", "device_kind",
...}.  No chip is an error: the process exits non-zero and prints no
result.  Any phase that raises fails the run.  (ROADMAP S1 replaces the
stages below with a table of cells; ``chip_smoke.py`` is the proof that
the two paid-for paths start on the chip.)

The preset is chosen to fit the attached chip's HBM (the north-star 1.3B
config needs >= ~32GB with AdamW; a v5e-16G chip runs 760M).  The baseline
is the A100 planning estimate from BASELINE.md, FLOPs-scaled to the chosen
model size: tokens/sec/chip ~= MFU * peak_flops / (6 * N_params) with the
A100 row at 45% MFU of 312 bf16 TFLOPs (which reproduces the 15-20k
tok/s/chip figure for 1.3B).  vs_baseline > 1.0 beats the reference chip-
for-chip at the same model.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

A100_PEAK_BF16 = 312e12
A100_MFU_EST = 0.45

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO_ROOT)


def _baseline_tokens_per_sec(n_params: float) -> float:
    return A100_MFU_EST * A100_PEAK_BF16 / (6.0 * n_params)


def _param_count(cfg) -> int:
    H, L, V, S = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.max_position_embeddings)
    return V * H + S * H + L * (12 * H * H + 13 * H) + 2 * H


def _measure(preset, seq, batch, steps, warmup, on_tpu, devices):
    """Train-step throughput for one (preset, seq, batch) config."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.jit import train_step
    from paddle_tpu.models import GPTForPretraining, gpt_config

    paddle.seed(0)
    cfg = gpt_config(preset, max_position_embeddings=seq,
                     hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                     use_recompute=on_tpu)
    model = GPTForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          weight_decay=0.01, multi_precision=True)
    if on_tpu:
        # amp O2: bf16 params feeding the MXU, fp32 master weights
        model, optimizer = amp.decorate(models=model, optimizers=optimizer,
                                        level="O2", dtype="bfloat16")

    def _step_fn(m, ids, labels):
        # O2 is pure-half: the auto_cast hook must be live DURING the
        # trace so every op (incl. post-LayerNorm matmuls) runs bf16 —
        # decorate() alone only casts parameters
        if on_tpu:
            with amp.auto_cast(enable=True, level="O2", dtype="bfloat16"):
                return m.loss_fn(m(ids), labels)
        return m.loss_fn(m(ids), labels)

    step = train_step(model, None, optimizer, step_fn=_step_fn)

    from paddle_tpu.core.dispatch import observe_op_stream
    from paddle_tpu.observability.metrics import (HistogramValue,
                                                  TIME_BUCKETS)

    rs = np.random.RandomState(0)
    dispatch_ops = {}

    def _count_op(ev):
        dispatch_ops[ev.op_name] = dispatch_ops.get(ev.op_name, 0) + 1

    ids = rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    labels = rs.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
    # first warmup step = trace + XLA compile + one step (the cold-start
    # number the persistent compile cache shrinks on re-runs).  The op
    # stream of this trace is ALSO where every op the compiled step
    # contains gets dispatched once — count it for the observability
    # snapshot (steady-state steps dispatch nothing; that's the point of
    # the jit)
    t_cold = time.perf_counter()
    with observe_op_stream(_count_op):
        step(ids, labels).block_until_ready()
    cold_compile_s = time.perf_counter() - t_cold
    for _ in range(max(warmup - 1, 0)):
        step(ids, labels).block_until_ready()
    step_hist = HistogramValue(TIME_BUCKETS)
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        t1 = time.perf_counter()
        loss = step(ids, labels)
        step_hist.observe(time.perf_counter() - t1)
    loss.block_until_ready()
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_chips = sum(1 for d in devices if d.platform != "cpu") or 1
    value = tokens_per_sec / (n_chips if on_tpu else 1)
    n_params = _param_count(cfg)
    res = {
        "preset": preset, "n_params": n_params,
        "batch": batch, "seq": seq, "steps": steps,
        "tokens_per_sec_per_chip": round(value, 2),
        "vs_baseline": round(value / _baseline_tokens_per_sec(n_params),
                             4),
        # cold vs warm start: first-step (trace+compile) wall seconds vs
        # steady-state step seconds — the gap is what the persistent
        # tuning/compile caches reclaim on re-runs
        "cold_compile_s": round(cold_compile_s, 3),
        "warm_step_s": round(dt / steps, 4),
        # observability snapshot: per-step DISPATCH time distribution
        # (async — the sync cost sits on the final block), and the op
        # stream the compiled step was traced from
        "observability": {
            "step_dispatch": step_hist.summary(),
            "dispatch_ops_total": sum(dispatch_ops.values()),
            "dispatch_top_ops": sorted(dispatch_ops.items(),
                                       key=lambda kv: -kv[1])[:8],
        },
    }
    if on_tpu:
        from paddle_tpu.device import chip_peak_flops
        res["mfu"] = round(value * 6.0 * n_params
                           / chip_peak_flops(devices[0]), 4)
    return res


def _measure_program_passes(on_tpu):
    """Op-count reduction + replay-time delta of the program-pass
    pipeline (FLAGS_program_passes) on a captured GPT decode step —
    the static-analysis subsystem's perf claim.  Tiny model: the
    metric is the graph-level reduction ratio, which is shape-
    independent, and the stage must fit the CPU-smoke budget."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.analysis.pass_check import check_equivalence
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.static.passes import (capture_decode_program,
                                          run_program_passes)
    paddle.seed(0)
    cfg = GPTConfig(num_layers=4, hidden_size=64, num_heads=4,
                    vocab_size=512, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    ids = Tensor(np.random.RandomState(0)
                 .randint(0, 512, (2, 8)).astype("int64"))
    prog, feed_names, fetches, tok = capture_decode_program(model, ids)
    opt, report = run_program_passes(prog, fetches, label="gpt_decode")
    equiv = check_equivalence(prog, opt, feed_names, fetches, [tok])

    def _replay_s(program, reps=8):
        pure, ext = program.build_replay(feed_names, fetches)
        ext_arrays = tuple(t._data for t in ext)
        pure((tok,), ext_arrays)                       # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = pure((tok,), ext_arrays)
        for o in out:
            o.block_until_ready()
        return (time.perf_counter() - t0) / reps

    before_s, after_s = _replay_s(prog), _replay_s(opt)
    return {
        "program": "gpt_decode_step",
        "ops_before": report["ops_before"],
        "ops_after": report["ops_after"],
        "reduction_pct": report["reduction_pct"],
        "allclose": bool(equiv["allclose"]),
        "fusion_hints": len(opt.fusion_hints),
        # eager (unjitted) replay = the per-step dispatch cost the
        # pass pipeline shrinks; warm_step_delta_pct < 0 is faster
        "replay_ms_before": round(before_s * 1e3, 3),
        "replay_ms_after": round(after_s * 1e3, 3),
        "warm_step_delta_pct": round(
            100.0 * (after_s - before_s) / before_s, 2) if before_s
        else 0.0,
    }


def _measure_megakernel_decode(on_tpu):
    """Eager vs compiled (FLAGS_megakernel_decode) decode on the same
    model/prompt: tokens/sec, per-token dispatch count, and the
    dispatch-interval histogram (the per-step dispatch-time metric the
    ROADMAP's mega-kernel item targets).  The compiled loop dispatches
    only the prefill — its per-token dispatch count is constant in
    max_new_tokens, which is the zero-host-transfer claim."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import observe_op_stream
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.observability.metrics import (HistogramValue,
                                                  TIME_BUCKETS)
    paddle.seed(0)
    cfg = GPTConfig(num_layers=4, hidden_size=128, num_heads=4,
                    vocab_size=512, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    batch, prompt_len, n_new = 4, 16, 16
    ids = Tensor(np.random.RandomState(0)
                 .randint(0, 512, (batch, prompt_len)).astype("int64"))

    def run(megakernel):
        ops = {"n": 0, "last_t": None}
        hist = HistogramValue(TIME_BUCKETS)

        def _count(ev):
            t = time.perf_counter()
            if ops["last_t"] is not None:
                hist.observe(t - ops["last_t"])
            ops["last_t"] = t
            ops["n"] += 1

        # warm call pays trace + compile; the timed call is steady state
        model.generate(ids, max_new_tokens=n_new,
                       _megakernel=megakernel)
        t0 = time.perf_counter()
        with observe_op_stream(_count):
            out = model.generate(ids, max_new_tokens=n_new,
                                 _megakernel=megakernel)
        out._data.block_until_ready()
        return time.perf_counter() - t0, ops["n"], hist, out

    eager_s, eager_ops, eager_hist, out_e = run(False)
    comp_s, comp_ops, _, out_c = run(True)
    eager_per_tok = eager_ops / n_new
    comp_per_tok = comp_ops / n_new
    return {
        "model": "gpt-4l-h128", "batch": batch,
        "prompt_len": prompt_len, "new_tokens": n_new,
        "eager_tokens_per_sec": round(batch * n_new / eager_s, 2),
        "compiled_tokens_per_sec": round(batch * n_new / comp_s, 2),
        "speedup": round(eager_s / comp_s, 3),
        "eager_dispatch_per_token": round(eager_per_tok, 2),
        "compiled_dispatch_per_token": round(comp_per_tok, 2),
        "dispatch_reduction_x": round(
            eager_per_tok / max(comp_per_tok, 1e-9), 1),
        "eager_dispatch_intervals": eager_hist.summary(),
        "tokens_match": bool(np.array_equal(np.asarray(out_e._data),
                                            np.asarray(out_c._data))),
    }


def _measure_serving(on_tpu):
    """Continuous-batching serving engine vs sequential generate():
    aggregate tokens/sec and p50/p99 request latency at N concurrent
    streams (the paddle_tpu.serving acceptance metric — the engine
    must beat the sequential baseline >= 2x at >= 8 streams on the
    CPU smoke config).  Latency quantiles come straight from the
    engine's registry histograms.

    The engine side runs TWICE — single-step (FLAGS_serving_fused_steps
    = 1) and fused persistent-program windows — with the dispatch-stream
    ``serving_host_sync`` markers counted per run, so
    ``host_syncs_per_100_tokens`` and ``steps_per_dispatch`` report the
    fused win as a measured number."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.core.dispatch import observe_op_stream
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.flags import get_flags, set_flags
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.engine import _REQ_LATENCY, _TTFT

    paddle.seed(0)
    cfg = GPTConfig(num_layers=4, hidden_size=128, num_heads=4,
                    vocab_size=512, max_position_embeddings=128,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    n_streams, prompt_len, n_new = 8, 16, 16
    fused_steps = 8
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, (prompt_len,)).tolist()
               for _ in range(n_streams)]

    # sequential baseline: one eager generate() per request, one after
    # another (the pre-engine serving shape); warm once for compiles
    model.generate(Tensor(np.asarray([prompts[0]], "int64")),
                   max_new_tokens=n_new)
    t0 = time.perf_counter()
    for p in prompts:
        model.generate(Tensor(np.asarray([p], "int64")),
                       max_new_tokens=n_new)
    seq_s = time.perf_counter() - t0
    seq_tps = n_streams * n_new / seq_s

    def _engine_run(n_fused, sanitizer=False):
        """One timed engine pass at FLAGS_serving_fused_steps=n_fused;
        host syncs + iterations counted off the dispatch stream.
        ``sanitizer=True`` runs the same traffic with
        FLAGS_lock_sanitizer on (instrumented locks) for the overhead
        comparison."""
        marks = {"syncs": 0, "steps": 0}

        def _hook(ev):
            if ev.op_name == "serving_host_sync":
                marks["syncs"] += 1
                marks["steps"] += int(ev.in_avals[0][0][0])

        keep = get_flags(["FLAGS_serving_fused_steps",
                          "FLAGS_lock_sanitizer"])
        set_flags({"FLAGS_serving_fused_steps": n_fused,
                   "FLAGS_lock_sanitizer": bool(sanitizer)})
        if sanitizer:
            from paddle_tpu.observability.lockwatch import \
                reset_lockwatch
            reset_lockwatch()
        try:
            engine = ServingEngine(model, max_batch=n_streams,
                                   page_size=16, prefix_caching=False)
            with engine:
                # warm the prefill + decode (+ fused window) programs
                # outside the timing
                engine.submit(prompts[0],
                              max_new_tokens=4).wait(timeout=120)
                lat_before = _REQ_LATENCY.labels(
                    engine=engine.engine_id).hist.count
                with observe_op_stream(_hook):
                    t0 = time.perf_counter()
                    reqs = []

                    def _one(p):
                        reqs.append(engine.submit(p,
                                                  max_new_tokens=n_new))

                    threads = [threading.Thread(target=_one, args=(p,))
                               for p in prompts]
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    for r in list(reqs):
                        r.wait(timeout=300)
                    eng_s = time.perf_counter() - t0
                lat = _REQ_LATENCY.labels(engine=engine.engine_id).hist
                ttft = _TTFT.labels(engine=engine.engine_id).hist
                stats = engine.stats()
        finally:
            set_flags(keep)
        total = n_streams * n_new
        return {
            "tokens_per_sec": round(total / eng_s, 2),
            "steps_per_sec": round(marks["steps"] / eng_s, 2),
            "host_syncs": marks["syncs"],
            "host_syncs_per_100_tokens": round(
                100.0 * marks["syncs"] / total, 2),
            "steps_per_dispatch": round(
                marks["steps"] / max(marks["syncs"], 1), 2),
            "request_latency": lat.summary(),
            "ttft": ttft.summary(),
            "timed_requests": lat.count - lat_before,
            "engine_stats": stats,
        }

    single = _engine_run(1)
    fused = _engine_run(fused_steps)
    # lock-sanitizer overhead gate: the same fused traffic with
    # FLAGS_lock_sanitizer on — instrumented locks (order-graph check
    # per acquire) must cost < 15% tokens/sec, or the chaos tier gets
    # too slow to run the sanitizer by default
    sanitized = _engine_run(fused_steps, sanitizer=True)
    tps_off = fused["tokens_per_sec"]
    tps_on = sanitized["tokens_per_sec"]
    overhead = max(0.0, 1.0 - tps_on / max(tps_off, 1e-9))
    assert overhead < 0.15, (
        f"lock sanitizer overhead {overhead:.1%} >= 15% "
        f"({tps_on} vs {tps_off} tokens/sec)")
    eng_tps = single["tokens_per_sec"]
    return {
        "model": "gpt-4l-h128", "streams": n_streams,
        "prompt_len": prompt_len, "new_tokens": n_new,
        "sequential_tokens_per_sec": round(seq_tps, 2),
        "engine_tokens_per_sec": eng_tps,
        "speedup": round(eng_tps / seq_tps, 3),
        # the persistent-program serving step, before/after: same
        # traffic, FLAGS_serving_fused_steps=1 vs =8
        "single_step": single,
        "fused": dict(fused, fused_steps_flag=fused_steps),
        "fused_speedup": round(
            fused["tokens_per_sec"] / max(eng_tps, 1e-9), 3),
        "host_sync_reduction": round(
            single["host_syncs"] / max(fused["host_syncs"], 1), 2),
        "lock_sanitizer": {
            "tokens_per_sec_off": tps_off,
            "tokens_per_sec_on": tps_on,
            "overhead_frac": round(overhead, 4),
        },
    }


def _measure_decode(on_tpu):
    """Decode tokens/sec through the paged KV cache (serving axis):
    batch-8 greedy decode on a 125M-class decoder."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    paddle.seed(0)
    cfg = GPTConfig(num_layers=12, hidden_size=768, num_heads=12,
                    vocab_size=50304, max_position_embeddings=256,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    ids = Tensor(np.random.RandomState(0)
                 .randint(0, 1000, (8, 32)).astype("int64"))
    # warm once (compiles), then time
    model.generate(ids, max_new_tokens=4, decode_strategy="greedy",
                   use_paged_cache=True)
    n_new = 16
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=n_new, decode_strategy="greedy",
                   use_paged_cache=True)
    dt = time.perf_counter() - t0
    return {"metric": "decode_tokens_per_sec",
            "value": round(8 * n_new / dt, 2),
            "batch": 8, "new_tokens": n_new,
            "platform": "tpu" if on_tpu else "cpu",
            "paged_cache": True}


def _measure_fleet(on_tpu):
    """Fleet router over 1 vs 2 real replica subprocesses: aggregate
    tokens/sec and affinity-hit rate under shared-prefix traffic (the
    serving.fleet acceptance metric).  Opt-in (BENCH_FLEET=1) — every
    replica pays a full interpreter + engine start, so the stage costs
    tens of seconds even on the CPU smoke config."""
    import threading

    from paddle_tpu.inference.serving import generate_http
    from paddle_tpu.serving.fleet import FleetRouter, ReplicaSupervisor

    n_requests, n_new, page = 16, 12, 16
    rs = np.random.RandomState(0)
    # two full shared pages, then a per-request tail: consecutive
    # requests for the same prefix should land on the page owner
    shared = rs.randint(0, 256, (2 * page,)).tolist()
    prompts = [shared + rs.randint(0, 256, (4,)).tolist()
               for _ in range(n_requests)]
    worker_args = ["--layers", "2", "--hidden", "64", "--heads", "4",
                   "--vocab", "256", "--max-pos", "128",
                   "--max-batch", "8", "--page-size", str(page)]

    def one(n_replicas):
        sup = ReplicaSupervisor(n_replicas, worker_args=worker_args)
        with sup, FleetRouter(sup, page_size=page) as router:
            # warm each replica's prefill/decode programs off the clock
            for h in sup.replicas:
                list(generate_http(h.url, shared[:8], max_new_tokens=2,
                                   timeout=300.0))
            counts = []
            lock = threading.Lock()

            def _one(p):
                toks = list(generate_http(router.url, p,
                                          max_new_tokens=n_new,
                                          timeout=300.0))
                with lock:
                    counts.append(len(toks))

            t0 = time.perf_counter()
            threads = [threading.Thread(target=_one, args=(p,))
                       for p in prompts]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            stats = router.fleet_stats()
        total = sum(counts)
        return {"replicas": n_replicas,
                "requests": n_requests,
                "tokens": total,
                "tokens_per_sec": round(total / dt, 2),
                "affinity_hits": stats["affinity_hits"],
                "affinity_hit_rate": round(
                    stats["affinity_hits"] / max(stats["served"], 1), 3),
                "resubmitted": stats["resubmitted"]}

    single = one(1)
    double = one(2)
    return {
        "model": "gpt-2l-h64", "new_tokens": n_new,
        "shared_prefix_pages": 2,
        "single": single, "double": double,
        "scaling": round(double["tokens_per_sec"]
                         / max(single["tokens_per_sec"], 1e-9), 3),
    }


def _measure_chaos(on_tpu):
    """Fault-containment drill: SIGSTOP one of two replicas while
    streams are in flight — the stalled legs hit the router's stream
    timeout, resubmit to the survivor with generated-so-far kept, and
    every stream must finish token-identical to an undisturbed
    reference pass (zero truncation).  Reports the SIGSTOP → all-
    streams-recovered latency.  Opt-in (BENCH_CHAOS=1): the stage
    costs replica startups plus the deliberate stall."""
    import signal
    import threading

    from paddle_tpu.inference.serving import generate_http
    from paddle_tpu.serving.fleet import FleetRouter, ReplicaSupervisor

    n_requests, n_new, page = 8, 24, 16
    leg_timeout = 4.0
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 256, (8,)).tolist()
               for _ in range(n_requests)]
    worker_args = ["--layers", "2", "--hidden", "64", "--heads", "4",
                   "--vocab", "256", "--max-pos", "128",
                   "--max-batch", "8", "--page-size", str(page)]
    sup = ReplicaSupervisor(2, worker_args=worker_args)
    with sup, FleetRouter(sup, page_size=page,
                          stream_timeout=leg_timeout) as router:
        # warm every replica's programs off the clock, then take an
        # UNDISTURBED reference pass through the router — replicas are
        # interchangeable under deterministic decode, so the chaos
        # pass must reproduce these streams token for token
        for h in sup.replicas:
            list(generate_http(h.url, prompts[0][:4], max_new_tokens=2,
                               timeout=300.0))
        want = [list(generate_http(router.url, p, max_new_tokens=n_new,
                                   timeout=300.0))
                for p in prompts]
        got = {}
        done_at = {}
        lock = threading.Lock()

        def _one(i, p):
            toks = list(generate_http(router.url, p,
                                      max_new_tokens=n_new,
                                      timeout=300.0))
            with lock:
                got[i] = toks
                done_at[i] = time.perf_counter()

        threads = [threading.Thread(target=_one, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        time.sleep(0.05)                    # streams in flight
        victim = sup.replicas[0]
        pid = victim.proc.pid
        t_stop = time.perf_counter()
        os.kill(pid, signal.SIGSTOP)
        try:
            for t in threads:
                t.join()
        finally:
            os.kill(pid, signal.SIGCONT)
        stats = router.fleet_stats()
    recovered = max(done_at.values()) - t_stop
    parity = [got[i] == want[i] for i in range(n_requests)]
    return {
        "model": "gpt-2l-h64", "requests": n_requests,
        "new_tokens": n_new,
        "stalled_replica": victim.id,
        "leg_timeout_s": leg_timeout,
        "resubmitted": stats["resubmitted"],
        "recovery_s": round(recovered, 3),
        "token_parity": all(parity),
        "truncated_streams": sum(
            1 for t in got.values() if len(t) != n_new),
    }


def run_bench():
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; jax reports platform "
                         f"{devices[0].platform!r}")
    if os.environ.get("BENCH_FLEET") == "1" \
            or os.environ.get("BENCH_CHAOS") == "1":
        # this process holds the chip, so replica children could not
        # reach it (ROADMAP R1: replicas must own their chips first)
        raise SystemExit("BENCH_FLEET / BENCH_CHAOS start replica "
                         "processes, which cannot share the chip this "
                         "process holds")
    on_tpu = True
    platform = devices[0].platform

    hbm = devices[0].memory_stats()["bytes_limit"]
    if os.environ.get("BENCH_PRESET"):
        preset = os.environ["BENCH_PRESET"]
    elif hbm >= 30e9:
        preset = "gpt3-1.3B"
    elif hbm >= 14e9:
        preset = "gpt3-760M"
    else:
        preset = "gpt3-350M"
    seq = int(os.environ.get("BENCH_SEQ", "2048"))
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    steps = int(os.environ.get("BENCH_STEPS", "5"))
    warmup = 2

    # count backend compile events (jax.monitoring) across the run —
    # the cold/warm split cache PRs optimize shows up here as a count
    compile_events = {"n": 0, "secs": 0.0}
    import jax.monitoring as _mon

    def _on_dur(event, duration, **kw):
        if "backend_compile" in event or "compilation_cache" in event:
            compile_events["n"] += 1
            compile_events["secs"] += float(duration)

    _mon.register_event_duration_secs_listener(_on_dur)

    primary = _measure(preset, seq, batch, steps, warmup, on_tpu, devices)
    out = {
        "metric": f"{preset}_pretrain_tokens_per_sec_per_chip",
        "value": primary["tokens_per_sec_per_chip"],
        "unit": "tokens/sec/chip",
        "vs_baseline": primary["vs_baseline"],
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "n_devices": len(devices),
        "preset": preset,
        "n_params": primary["n_params"],
        "batch": primary["batch"], "seq": seq, "steps": steps,
        "pallas_attention": bool(
            __import__("paddle_tpu.flags", fromlist=["get_flag"])
            .get_flag("use_pallas_attention")),
        "mfu": primary["mfu"],
        "cold_compile_s": primary["cold_compile_s"],
        "warm_step_s": primary["warm_step_s"],
    }
    out["observability"] = dict(
        primary["observability"],
        compile_events=compile_events["n"],
        compile_total_s=round(compile_events["secs"], 3))
    from paddle_tpu.tuning.cache import cache_stats
    out["tuning_cache"] = cache_stats()

    # program-pass pipeline on the captured GPT decode step: op-count
    # reduction + replay-time delta (static/passes)
    out["program_passes"] = _measure_program_passes(on_tpu)

    # mega-kernel decode: eager vs compiled lax.while_loop generation
    # (FLAGS_megakernel_decode) — tokens/sec + per-token dispatch count
    out["megakernel_decode"] = _measure_megakernel_decode(on_tpu)

    # continuous-batching serving: engine vs sequential generate() at
    # 8 concurrent streams + registry latency histograms.  The stage
    # runs with a SCRATCH observability dir so the run produces its own
    # event log (batch_step spans, admits) — the SLO watchdog then
    # self-gates the log (tail vs head of each duration key).  Only
    # this stage pays the event-log overhead, and both sides of its
    # engine-vs-sequential comparison pay it equally.
    import shutil
    import tempfile
    from paddle_tpu.flags import set_flags
    from paddle_tpu.observability import read_events
    from paddle_tpu.observability import watchdog as _watchdog
    obs_dir = tempfile.mkdtemp(prefix="bench-obs-")
    try:
        set_flags({"FLAGS_observability_dir": obs_dir})
        try:
            out["serving"] = _measure_serving(on_tpu)
        finally:
            set_flags({"FLAGS_observability_dir": ""})
        recs = read_events(obs_dir)
        # load-shaped keys (queue wait, whole-request latency) are
        # excluded by watchdog.DEFAULT_EXCLUDE — gate on WORK durations
        # only; a flagged key marks the run for triage
        flagged = _watchdog.self_check(recs)
        out["watchdog"] = {"events": len(recs), "regressions": flagged,
                           "status": "fail" if flagged else "ok"}
        # learned-perf-model divergence verdict: fit a model on the
        # stage's own telemetry, then check the same log against its
        # predictions — proves the fit → predict → watchdog loop end to
        # end (a healthy run agrees with a model trained on itself)
        from paddle_tpu.tuning.learned import fit_from_telemetry
        model, fit_summary = fit_from_telemetry(None, [obs_dir],
                                                min_samples=8)
        if model.heads:
            mfind = _watchdog.model_check(recs, model, emit_events=False)
            out["watchdog"]["model"] = {
                "heads": sorted(model.heads),
                "fit": {k: v for k, v in fit_summary.items()
                        if k in model.heads},
                "regressions": mfind,
                "status": "fail" if mfind else "ok"}
        else:
            out["watchdog"]["model"] = {
                "skipped": "not enough telemetry", "fit": fit_summary}
    finally:
        shutil.rmtree(obs_dir, ignore_errors=True)

    # per-config table: a second model size, and (opt-in) decode
    # throughput through the paged cache
    if os.environ.get("BENCH_EXTRA", "1") == "1":
        extras = {}
        if preset != "gpt3-125M":
            extras["gpt3-125M"] = _measure("gpt3-125M", seq, batch, 3, 1,
                                           on_tpu, devices)
        if os.environ.get("BENCH_DECODE") == "1":
            extras["decode"] = _measure_decode(on_tpu)
        if extras:
            out["configs"] = extras
    print(json.dumps(out))


if __name__ == "__main__":
    run_bench()
