"""Runner "train": the jitted train step, kept queued for the window.

The body is ``chip_smoke.py``'s ``_train_step`` / ``_run_steps`` (proven
on the chip in PR 21), copied so that a later change to the smoke cannot
move the yardstick: AdamW with float32 master weights, ``amp.decorate``
at O2 in bfloat16, ``jit.train_step`` over a step function that keeps
``auto_cast`` live during the trace.

The loop keeps the job's ``steps_in_flight`` steps dispatched and reads
each loss in order, as a training loop that logs its losses does: the
device always has the next step queued, so the host's time between two
steps (its read of a loss, its next dispatch, a stall of a shared core)
is hidden unless it outlasts the queued steps.  Every loss is read on
the host before the clock stops, so the clock covers all the device's
work.
"""
from __future__ import annotations

import collections
import math
import time
from typing import Any, Deque, Dict, List

from benchmark import flops, generator, harness

# losses read before the traced run's profiler starts (the window's first
# steps are left to settle), and losses read while it runs
_TRACE_FROM, _TRACE_STEPS = 3, 5


def _train_step(model, settings: Dict[str, Any]):
    import paddle_tpu.optimizer as opt
    from paddle_tpu import amp
    from paddle_tpu.jit import train_step
    if settings["optimizer"] != "adamw" or settings["amp_level"] != "O2":
        raise harness.BenchmarkError(
            "runner 'train' builds AdamW under AMP O2; a configuration "
            "that trains otherwise brings a runner of its own")
    optimizer = opt.AdamW(
        learning_rate=settings["learning_rate"],
        parameters=model.parameters(),
        weight_decay=settings["weight_decay"],
        multi_precision=settings["multi_precision"])
    model, optimizer = amp.decorate(models=model, optimizers=optimizer,
                                    level="O2", dtype=settings["amp_dtype"])

    def step_fn(m, ids, labels):
        # O2 is pure-half: the auto_cast hook must be live during the
        # trace, decorate() alone only casts parameters
        with amp.auto_cast(enable=True, level="O2",
                           dtype=settings["amp_dtype"]):
            return m.loss_fn(m(ids), labels)

    return train_step(model, None, optimizer, step_fn=step_fn)


def _steps(step, batches, first: int, in_flight: int, seconds: float,
           losses: List[float], profiler=None):
    """Runs steps from batch ``first`` on until ``seconds`` have passed
    (at least one), ``in_flight`` of them dispatched at a time, and reads
    every loss into ``losses``.  Returns the seconds from each loss's
    arrival to the next (the device's step while another is queued behind
    it; they add up to the time of the call) and the indices of those
    that the profiler's start, run or stop touched."""
    import jax
    pending: Deque[Any] = collections.deque()
    step_s: List[float] = []
    traced: List[int] = []
    t0 = last = time.perf_counter()

    def read_loss() -> None:
        nonlocal last
        with jax.profiler.TraceAnnotation("bench:loss_read"):
            losses.append(float(pending.popleft()))
        now = time.perf_counter()
        step_s.append(now - last)
        last = now
        if profiler and profiler.running:
            traced.append(len(step_s) - 1)
            if len(traced) == _TRACE_STEPS:
                profiler.stop()
                traced.append(len(step_s))       # the next pays the stop
        elif profiler and not traced and len(step_s) == _TRACE_FROM:
            profiler.start()

    i = first
    while i == first or time.perf_counter() - t0 < seconds:
        ids, labels = batches[i % len(batches)]
        with jax.profiler.TraceAnnotation("bench:train_step_dispatch"):
            pending.append(step(ids, labels))
        i += 1
        if len(pending) >= in_flight:
            read_loss()
    while pending:
        read_loss()
    if profiler:
        profiler.stop()
    return step_s, traced


def run(cell: Dict[str, Any], args, clock: harness.SetupClock) -> str:
    import jax
    import jax.numpy as jnp
    device = harness.require_device(cell["chips"], args.rehearse)
    compiles = harness.CompileCounter()
    cfg, job = cell["config"], cell["traffic"]
    builder = harness.builder_for(cfg)
    failures: List[str] = []

    model = builder.build(cfg, args.seed, training=True)
    clock.mark("model built")
    batches = generator.train_batches(job, cfg["vocab_size"], args.seed)
    tokens_per_step = int(job["batch"]) * int(job["seq"])

    # the reference's float32 loss of the first batch under the initial
    # weights, taken before AMP casts the model's parameters
    ids0, labels0 = batches[0]
    ref_loss = float(jax.jit(
        lambda w, i, l: builder.reference_loss(w, i, l, cfg))(
            builder.weights(model), jnp.asarray(ids0), jnp.asarray(labels0)))
    clock.mark(f"reference loss {ref_loss:.4f}")

    step = _train_step(model, cfg["train"])
    losses: List[float] = []
    # TrainStep traces twice: step 0 creates the optimizer state inside
    # the trace (bootstrap), step 1 takes it as input (steady)
    for i in range(2):
        t = time.perf_counter()
        _steps(step, batches, i, 1, 0.0, losses)
        clock.mark(f"warm step {i}: {time.perf_counter() - t:.2f} s, "
                   f"loss {losses[-1]:.4f}")
    tol = builder.tolerances()["loss"]
    harness.check(abs(losses[0] - ref_loss) <= tol,
                  f"first loss {losses[0]:.4f} within {tol} of the float32 "
                  f"reference {ref_loss:.4f}", failures)

    profiler = harness.Profiler(args.out) if args.trace else None
    compiled_before = compiles.count
    clock.window_starts()
    t0 = time.perf_counter()
    step_s, traced = _steps(step, batches, 2, int(job["steps_in_flight"]),
                            args.seconds, losses, profiler)
    window_s = time.perf_counter() - t0
    n = len(step_s)
    in_window = compiles.count - compiled_before

    tokens_per_s = n * tokens_per_step / window_s
    print(f"window: {n} steps of {tokens_per_step} tokens in "
          f"{window_s:.3f} s, loss to loss min {min(step_s) * 1e3:.1f} "
          f"median {harness.median(step_s) * 1e3:.1f} max "
          f"{max(step_s) * 1e3:.1f} ms; losses first {losses[:4]} last "
          f"{losses[-4:]}; "
          f"backend compiles in set-up {compiled_before} (cache hits "
          f"{compiles.cache_hits}), in the window {in_window}", flush=True)
    harness.check(in_window == 0, "nothing compiled inside the window",
                  failures)
    harness.check(all(math.isfinite(x) for x in losses),
                  "every loss is finite", failures)
    k = min(8, len(losses) // 2)
    harness.check(harness.median(losses[-k:]) < harness.median(losses[:k]),
                  f"median of the last {k} losses "
                  f"{harness.median(losses[-k:]):.4f} below the first "
                  f"{k}'s {harness.median(losses[:k]):.4f}", failures)

    layer, breakdown = {}, None
    if args.trace:
        steady = [s for i, s in enumerate(step_s) if i not in traced]
        observed: Dict[str, Any] = {
            "step_s": steady or step_s, "tokens_per_step": tokens_per_step,
            "flops_per_token": flops.train_flops_per_token(
                builder.flops_shape(cfg), int(job["seq"])),
            # no peak for a CPU: a rehearsal reports no MFU
            "peak_flops": None if args.rehearse else harness.peaks_for(
                device["kind"])["bf16_flops"] * cell["chips"]}
        device.update(harness.traced_device(profiler, observed,
                                            args.rehearse))
        layer = harness.read_layer_metrics(cell["traffic_name"], observed)
        breakdown = harness.breakdown_of(observed)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    return harness.result_line(
        cell, bool(args.trace), correct=not failures, attempted=n, failed=0,
        metrics={"train_tokens_per_s": (tokens_per_s, "tokens/s"),
                 "setup_s": (clock.setup_s, "s")},
        layer_metrics=layer, device=device, breakdown=breakdown)
