"""Runner "serve": the serving engine under a closed or an open loop.

The body is ``chip_smoke.py``'s ``phase_serve`` without the HTTP front
(``ServingEngine.submit`` / ``Request.stream``, proven on the chip in PR
21), copied so that the smoke cannot move the yardstick.  Which loop is
data: the traffic file's ``"loop"`` is ``"closed"`` (``clients`` callers,
each sending its next request when the last one completed) or ``"open"``
(Poisson arrivals at the cell's fixed ``rate``, each request timed from
when it was due, a drain of ``drain_s`` after the window).

Requests are timed from the client's side: a thread per caller reads
``Request.stream()`` and stamps each token as it arrives.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import generator, harness

# the traced run's profiler covers this stretch of the window (seconds
# from its start), cut to the window where that is shorter
_TRACE_FROM_S, _TRACE_FOR_S = 5.0, 5.0
# prompts of the logits check (tokens), and the teacher-forced decode
# steps that follow each prefill
_CHECK_PROMPTS, _CHECK_DECODES = (96, 40), 4


# ---------------------------------------------------------------------------
# correctness before the window: logits against the plain reference
# ---------------------------------------------------------------------------

def check_logits(model, builder, cfg: Dict[str, Any], seed: int,
                 failures: List[str]) -> None:
    """Two seeded prompts through the model's own ragged step over fresh
    small pools — one prefill, then ``_CHECK_DECODES`` teacher-forced
    decode steps — and each step's last-row logits against the plain
    reference's full forward pass at the same positions."""
    import jax
    import jax.numpy as jnp
    params, step = model.build_ragged_decode_step()
    step = jax.jit(step)
    c = model.config
    ps = int(cfg["serve"]["page_size"])
    nkv = int(getattr(c, "num_kv_heads", None) or c.num_heads)
    hd = int(c.hidden_size) // int(c.num_heads)
    n_layers = len(params["blocks"] if "blocks" in params
                   else params["layers"])
    rs = generator.rng_for(seed, 4)
    lens = [min(n, int(c.max_position_embeddings) - _CHECK_DECODES - 1)
            for n in _CHECK_PROMPTS]
    seqs = [rs.randint(0, int(c.vocab_size), (n + _CHECK_DECODES,))
            for n in lens]
    b = len(seqs)
    qw = 1
    while qw < max(lens):
        qw <<= 1
    ppseq = -(-(max(lens) + _CHECK_DECODES) // ps)
    sink = b * ppseq
    dtype = cfg["serve"]["dtype"]
    pools = tuple((jnp.zeros((nkv, sink + 1, ps, hd), dtype),
                   jnp.zeros((nkv, sink + 1, ps, hd), dtype))
                  for _ in range(n_layers))
    tables = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)

    def feed(width: int, start: List[int], count: List[int]):
        tok = np.zeros((b, width), "int64")
        pos = np.zeros((b, width), "int32")
        page_ids = np.full((b, width), sink, "int32")
        slots = np.zeros((b, width), "int32")
        for i in range(b):
            p = np.arange(start[i], start[i] + count[i])
            tok[i, :count[i]] = seqs[i][p]
            pos[i, :count[i]] = p
            page_ids[i, :count[i]] = tables[i, p // ps]
            slots[i, :count[i]] = p % ps
        kv = np.asarray([s + n for s, n in zip(start, count)], "int32")
        return tok, pos, page_ids, slots, kv, np.asarray(count, "int32")

    got = []                       # [steps][b, V]
    tok, pos, page_ids, slots, kv, ql = feed(qw, [0] * b, lens)
    logits, pools = step(params, tok, pos, pools, page_ids, slots, kv, ql,
                         tables)
    got.append(np.asarray(logits, np.float32))
    for t in range(_CHECK_DECODES):
        tok, pos, page_ids, slots, kv, ql = feed(
            1, [n + t for n in lens], [1] * b)
        logits, pools = step(params, tok, pos, pools, page_ids, slots, kv,
                             ql, tables)
        got.append(np.asarray(logits, np.float32))
    del pools

    w = builder.weights(model)
    ref_fn = jax.jit(lambda w, ids: builder.reference_logits(w, ids, cfg))
    tol = builder.tolerances()["logits"]
    worst = 0.0
    for i, n in enumerate(lens):
        want = np.asarray(ref_fn(w, jnp.asarray(seqs[i])), np.float32)
        rows = want[n - 1:n + _CHECK_DECODES]
        mine = np.stack([g[i] for g in got])
        if not np.all(np.isfinite(mine)):
            worst = float("inf")
            continue
        worst = max(worst, float(np.max(np.abs(mine - rows))
                                 / (np.max(np.abs(rows)) + 1e-9)))
    harness.check(worst <= tol,
                  f"logits of prefill {lens} and {_CHECK_DECODES} decode "
                  f"steps through the ragged step against the float32 "
                  f"reference: max error {worst:.2e} of the largest logit, "
                  f"tolerance {tol}", failures)


# ---------------------------------------------------------------------------
# the loops
# ---------------------------------------------------------------------------

class _Record:
    """One request as its client saw it (perf_counter seconds)."""
    __slots__ = ("budget", "due", "sent", "times", "tokens", "error",
                 "finished")

    def __init__(self, budget: int, due: Optional[float] = None):
        self.budget, self.due = budget, due
        self.sent = 0.0
        self.times: List[float] = []
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.finished = False


def _consume(engine, request: Dict[str, Any], rec: _Record,
             timeout: float) -> None:
    rec.sent = time.perf_counter()
    req = engine.submit(request["prompt"],
                        max_new_tokens=request["max_new_tokens"])
    try:
        for tok in req.stream(timeout=timeout):
            rec.times.append(time.perf_counter())
            rec.tokens.append(int(tok))
        rec.finished = True
    except RuntimeError as e:      # what stream() raises on any failure
        rec.error = str(e)


def closed_loop(engine, reqs: generator.Requests, clients: int,
                t_end: float, records: List[_Record]
                ) -> List[threading.Thread]:
    """``clients`` callers, each sending the pool's next request as soon
    as its last one completed, until ``t_end``."""
    lock = threading.Lock()
    cursor = [0]

    def client():
        while time.perf_counter() < t_end:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            request = reqs.get(i)
            rec = _Record(request["max_new_tokens"])
            records.append(rec)
            _consume(engine, request, rec, timeout=120.0)
            if rec.error:
                return             # the engine stopped, or failed: no retry

    threads = [threading.Thread(target=client, daemon=True,
                                name=f"bench-client-{k}")
               for k in range(clients)]
    for t in threads:
        t.start()
    return threads


def open_loop(engine, reqs: generator.Requests, due: List[float],
              t0: float, records: List[_Record]) -> List[threading.Thread]:
    """Send request ``i`` at ``t0 + due[i]`` whatever the engine is
    doing; returns when the last one is sent."""
    threads = []
    for i, d in enumerate(due):
        request = reqs.get(i)
        wait = t0 + d - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = _Record(request["max_new_tokens"], due=t0 + d)
        records.append(rec)
        t = threading.Thread(target=_consume, daemon=True,
                             args=(engine, request, rec, 120.0))
        t.start()
        threads.append(t)
    return threads


def _join(threads: List[threading.Thread], until: float) -> None:
    for t in threads:
        t.join(timeout=max(0.0, until - time.perf_counter()))


# ---------------------------------------------------------------------------
# reduction of the records
# ---------------------------------------------------------------------------

def summarize(records: List[_Record], t0: float, t_end: float,
              vocab: int, cut_at_end: bool) -> Dict[str, Any]:
    """Counts and samples of one window.  With ``cut_at_end`` (closed
    loop) a request still running when the window closed is neither
    attempted nor failed: the window cut it, the system did not.  In an
    open loop every request due in the window is attempted and one that
    did not finish by the end of the drain failed."""
    done, failed, cut, bad = [], 0, 0, 0
    for r in records:
        ran_over = not r.finished or (r.times and r.times[-1] > t_end)
        if cut_at_end and ran_over and (
                r.error is None or "engine stopped" in r.error):
            cut += 1
        elif not r.finished:
            failed += 1
        else:
            done.append(r)
            if len(r.tokens) != r.budget or \
                    not all(0 <= t < vocab for t in r.tokens):
                bad += 1
    tokens = sum(1 for r in records for t in r.times if t0 <= t <= t_end)
    tpot = [v for v in (harness.tpot_ms(r.times) for r in done)
            if v is not None]
    ttft = [(r.times[0] - r.due) * 1e3 for r in done
            if r.due is not None and r.times]
    late = [r.sent - r.due for r in records if r.due is not None]
    return {"attempted": len(done) + failed, "failed": failed, "cut": cut,
            "bad": bad, "done": done, "tokens": tokens, "tpot_ms": tpot,
            "ttft_ms": ttft, "late_s": late}


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------

def _start_engine(cell, args, clock, failures):
    from paddle_tpu.flags import set_flags
    from paddle_tpu.serving import ServingEngine
    cfg, mix = cell["config"], cell["traffic"]
    builder = harness.builder_for(cfg)
    model = builder.build(cfg, args.seed, training=False)
    clock.mark("model built")
    check_logits(model, builder, cfg, args.seed, failures)
    clock.mark("logits checked")
    flags = {"FLAGS_serving_engine": True}
    if args.trace:
        flags["FLAGS_observability_dir"] = os.path.join(args.out, "events")
    set_flags(flags)
    s = cfg["serve"]
    engine = ServingEngine(model, max_batch=s["max_batch"],
                           page_size=s["page_size"],
                           num_pages=s["num_pages"], dtype=s["dtype"])
    engine.start()
    # warm exactly the programs this mix can reach: one request per
    # power-of-two prompt bucket (two tokens each, so the decode-only
    # program runs too), one after another
    vocab = int(cfg["vocab_size"])
    rs = generator.rng_for(args.seed, 5)
    limit = int(cfg["max_position_embeddings"]) - 4
    for q in generator.prompt_buckets(mix):
        t = time.perf_counter()
        out = engine.generate(rs.randint(0, vocab, (min(q, limit),)).tolist(),
                              max_new_tokens=2)
        clock.mark(f"warmed Q={q}: {time.perf_counter() - t:.2f} s, "
                   f"{len(out)} tokens")
    return engine, vocab


def _window(engine, cell, seed: int, seconds: float, rate, vocab: int,
            profiler: Optional[harness.Profiler]) -> Dict[str, Any]:
    """One measured window on a warm engine; returns the summary."""
    mix = cell["traffic"]
    records: List[_Record] = []
    if mix["loop"] == "closed":
        reqs = generator.Requests(mix, vocab, seed, int(mix["pool"]))
    else:
        due = generator.due_times(rate, seconds, seed)
        reqs = generator.Requests(mix, vocab, seed, len(due))
    wall0, t0 = time.time(), time.perf_counter()
    t_end = t0 + seconds
    tracer = None
    if profiler:
        start = min(_TRACE_FROM_S, seconds / 4.0)
        length = min(_TRACE_FOR_S, seconds / 2.0)

        def trace():
            time.sleep(max(0.0, t0 + start - time.perf_counter()))
            profiler.start()
            time.sleep(length)
            profiler.stop()
        tracer = threading.Thread(target=trace, name="bench-tracer")
        tracer.start()
    if mix["loop"] == "closed":
        threads = closed_loop(engine, reqs, int(mix["clients"]), t_end,
                              records)
    else:
        threads = open_loop(engine, reqs, due, t0, records)
    time.sleep(max(0.0, t_end - time.perf_counter()))
    wall_end = time.time()
    backlog = sum(1 for r in list(records) if not r.finished)
    stats = engine.stats()
    if mix["loop"] == "open":
        _join(threads, t_end + float(mix["drain_s"]))
    if tracer:
        tracer.join()
    out = summarize(list(records), t0, t_end, vocab,
                    cut_at_end=mix["loop"] == "closed")
    out.update(stats=stats, threads=threads, wall=(wall0, wall_end),
               seconds=seconds, t0=t0, backlog=backlog)
    return out


def _sweep(engine, cell, args, vocab: int) -> None:
    """Offer each rate for 20 s after the one set-up and print the
    table the knee is read from (PERF.md says how)."""
    print("sweep: rate sent finished failed unfinished_at_window_end "
          "ttft_first_third_ms ttft_last_third_ms ttft_p90_ms tpot_p90_ms "
          "tokens_per_s late_p99_ms", flush=True)
    max_batch = int(cell["config"]["serve"]["max_batch"])
    knee = None
    for k, rate in enumerate(float(r) for r in args.sweep.split(",")):
        w = _window(engine, cell, args.seed + k, 20.0, rate, vocab, None)
        done = sorted(w["done"], key=lambda r: r.due)
        third = [[(r.times[0] - r.due) * 1e3 for r in done
                  if lo <= r.due - w["t0"] < hi]
                 for lo, hi in ((0.0, 20.0 / 3), (40.0 / 3, 20.0))]
        med = [harness.median(t) if t else float("nan") for t in third]
        print(f"sweep: {rate:.2f} {w['attempted']} {len(done)} "
              f"{w['failed']} {w['backlog']} {med[0]:.1f} {med[1]:.1f} "
              f"{harness.percentile(w['ttft_ms'], 90):.1f} "
              f"{harness.percentile(w['tpot_ms'], 90):.1f} "
              f"{w['tokens'] / 20.0:.1f} "
              f"{harness.percentile(w['late_s'], 99) * 1e3:.2f}", flush=True)
        # the knee: the highest rate whose last third's median TTFT is at
        # most twice its first third's, with at most 2 x max_batch requests
        # unfinished when the window closed
        if med[1] <= 2.0 * med[0] and w["backlog"] <= 2 * max_batch \
                and w["failed"] == 0:
            knee = rate if knee is None else max(knee, rate)
        # let stragglers end before the next rate
        _join(w["threads"], time.perf_counter() + 30.0)
        if w["backlog"] > 4 * max_batch:
            print("sweep: far past the knee, higher rates left out",
                  flush=True)
            break
    if knee is not None:
        print(f"sweep: knee={knee:.2f} rate={round(0.8 * knee / 0.05) * 0.05:.2f}"
              f" (four fifths of the knee, rounded to 0.05/s)", flush=True)


def run(cell: Dict[str, Any], args, clock: harness.SetupClock) -> str:
    device = harness.require_device(cell["chips"], args.rehearse)
    compiles = harness.CompileCounter()
    cfg, mix = cell["config"], cell["traffic"]
    failures: List[str] = []
    if mix["loop"] == "open" and "rate" not in cell:
        raise harness.BenchmarkError(
            f"cells/{cell['name']}.json needs a fixed 'rate' for an open "
            f"loop")
    engine, vocab = _start_engine(cell, args, clock, failures)
    if args.sweep:
        _sweep(engine, cell, args, vocab)
        engine.stop(drain=False)
        return ""

    profiler = harness.Profiler(args.out) if args.trace else None
    programs_before = engine.stats()["programs"]
    compiled_before = compiles.count
    clock.window_starts()
    w = _window(engine, cell, args.seed, float(args.seconds),
                cell.get("rate"), vocab, profiler)
    in_window = compiles.count - compiled_before
    stats = w["stats"]
    engine.stop(drain=False)
    _join(w["threads"], time.perf_counter() + 10.0)

    print(f"window: {w['attempted']} requests attempted, {w['failed']} "
          f"failed, {w['cut']} cut by the window's end; {w['tokens']} "
          f"tokens in {w['seconds']:.1f} s; tpot samples "
          f"{len(w['tpot_ms'])}, ttft samples {len(w['ttft_ms'])}; backend "
          f"compiles in set-up {compiled_before} (cache hits "
          f"{compiles.cache_hits}), in the window {in_window}", flush=True)
    print(f"window: prompt lengths {generator.lengths(mix['prompt'], 8)} "
          f"output lengths {generator.lengths(mix['output'], 8)} (octiles "
          f"of the mix); engine stats {stats}", flush=True)
    harness.check(in_window == 0
                  and stats["programs"] == programs_before,
                  f"nothing compiled inside the window (programs "
                  f"{programs_before} -> {stats['programs']})", failures)
    harness.check(w["bad"] == 0 and len(w["done"]) > 0,
                  f"each of the {len(w['done'])} completed requests "
                  f"returned exactly its budget of ids in [0, {vocab}), "
                  f"none the -1 sentinel", failures)
    harness.check(stats["health"] == "ok" and stats["quarantined"] == 0
                  and stats["evictions"] == 0,
                  "engine health ok; nothing quarantined or evicted",
                  failures)

    metrics = {"setup_s": (clock.setup_s, "s"),
               "serve_tokens_per_s": (w["tokens"] / w["seconds"], "tokens/s")}
    if w["tpot_ms"]:
        metrics["tpot_p90_ms"] = (harness.percentile(w["tpot_ms"], 90.0),
                                  "ms")
    if w["ttft_ms"]:
        metrics["ttft_p90_ms"] = (harness.percentile(w["ttft_ms"], 90.0),
                                  "ms")

    layer, breakdown = {}, None
    if args.trace:
        from paddle_tpu.observability import read_events
        steps = [e for e in read_events(os.path.join(args.out, "events"),
                                        kinds=["batch_step"])
                 if not e.get("cold_start")
                 and w["wall"][0] <= e["ts"] <= w["wall"][1]]
        observed: Dict[str, Any] = {
            "batch_steps": steps, "max_batch": cfg["serve"]["max_batch"],
            "gen_late_s": w["late_s"], "tpot_ms": w["tpot_ms"]}
        print(f"trace: {len(steps)} batch_step events in the window",
              flush=True)
        device.update(harness.traced_device(profiler, observed,
                                            args.rehearse))
        layer = harness.read_layer_metrics(cell["traffic_name"], observed)
        breakdown = harness.breakdown_of(observed)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    return harness.result_line(
        cell, bool(args.trace), correct=not failures,
        attempted=w["attempted"], failed=w["failed"], metrics=metrics,
        layer_metrics=layer, device=device, breakdown=breakdown)
