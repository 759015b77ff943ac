"""Runner "serve_described": runner "serve" for a model whose ragged step
says what pools it takes.

The loops, the window, the reduction of the records and the sweep are
``runners/serve.py``'s, imported and not copied.  Three things differ:

* ``check_logits`` asks the program for its pools and tables
  (``step.cache``, a ``models.generation.CacheDescription``) instead of
  building one ``[nkv, P, ps, hidden // heads]`` pair a layer itself,
  prefills in chunks of the configuration's ``max_prefill_chunk`` as
  the engine will, and takes a step that returns a third value;
* the engine is started with the configuration's ``max_prefill_chunk``
  and ``prefix_caching`` (absent: 0 and true, the engine's defaults),
  and every chunk width the mix can reach is warmed: with a chunk of
  ``c`` a prompt of ``k c + r`` tokens runs steps of ``c`` and of ``r``;
* a traced run also sums the device's operation seconds by the
  program's kernel names (``layer_metrics/experts_window.py``) into
  ``observed["kernel_s"]``, and hands the readers the configuration.

What a configuration's builder has to provide for this runner:
``MODEL_KEYS``, ``build(cfg, seed, training)``, ``weights(model)``,
``reference_logits(w, ids, cfg)``, ``tolerances()`` and, if it has
something to say beside the logits' error, ``reference_logits_and_notes(
w, ids, cfg)`` (logits and a tree of notes from one forward pass) with
``reference_report(notes, rows)``.  The reference is causal: sequences
are padded to one length so that it compiles once.  The model's ``build_ragged_decode_step()`` returns a step
with ``step.cache``.  It runs ``mistral-7b-8l``'s files unchanged
(``tests/benchmark_tests/test_mimo_v2_cell.py``), so that a later
``benchmark`` PR can fold the two runners into one.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import generator, harness
from benchmark.runners import serve

# prompts of the logits check (tokens): the first crosses the window,
# a chunk boundary and several pages; then the teacher-forced decode
# steps that follow each prefill
_CHECK_PROMPTS, _CHECK_DECODES = (1500, 300), 4


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def check_logits(model, builder, cfg: Dict[str, Any], seed: int,
                 failures: List[str]) -> None:
    """Two seeded prompts through the model's own ragged step over fresh
    pools of the step's own description — prefill in chunks, then
    ``_CHECK_DECODES`` teacher-forced decode steps — and the last row's
    logits of each sequence's last prefill chunk and of every decode
    step against the plain reference's full forward pass."""
    import jax
    import jax.numpy as jnp
    params, step = model.build_ragged_decode_step()
    cache = step.cache
    step = jax.jit(step)
    c = model.config
    ps = int(cfg["serve"]["page_size"])
    max_pos = int(c.max_position_embeddings)
    chunk = int(cfg["serve"].get("max_prefill_chunk", 0)) or max_pos
    rs = generator.rng_for(seed, 4)
    lens = [min(n, max_pos - _CHECK_DECODES - 1) for n in _CHECK_PROMPTS]
    seqs = [rs.randint(0, int(c.vocab_size), (n + _CHECK_DECODES,))
            for n in lens]
    b = len(seqs)
    ppseq = -(-(max(lens) + _CHECK_DECODES) // ps)
    sink = b * ppseq
    ring_pages = cache.ring_pages(ps, chunk)
    pools = cache.new_pools(sink + 1, ps, cfg["serve"]["dtype"], b,
                            ring_pages)
    full = np.arange(b * ppseq, dtype="int32").reshape(b, ppseq)
    tables = cache.tables(full, np.arange(b), ring_pages)

    def feed(start: List[int], count: List[int]):
        width = _bucket(max(count))
        tok = np.zeros((b, width), "int64")
        pos = np.zeros((b, width), "int32")
        page_ids = np.full((b, width), sink, "int32")
        slots = np.zeros((b, width), "int32")
        for i in range(b):
            p = np.arange(start[i], start[i] + count[i])
            tok[i, :count[i]] = seqs[i][p]
            pos[i, :count[i]] = p
            page_ids[i, :count[i]] = full[i, p // ps]
            slots[i, :count[i]] = p % ps
        kv = np.asarray([s + n for s, n in zip(start, count)], "int32")
        return tok, pos, page_ids, slots, kv, np.asarray(count, "int32")

    got: List[List[np.ndarray]] = [[] for _ in seqs]    # [b][1 + decodes]
    done = [0] * b
    while any(d < n + _CHECK_DECODES for d, n in zip(done, lens)):
        count = [min(chunk, n - d) if d < n
                 else int(d < n + _CHECK_DECODES)
                 for d, n in zip(done, lens)]
        tok, pos, page_ids, slots, kv, ql = feed(done, count)
        out = step(params, tok, pos, pools, page_ids, slots, kv, ql,
                   tables)
        logits, pools = np.asarray(out[0], np.float32), out[1]
        for i in range(b):
            done[i] += count[i]
            if count[i] and done[i] >= lens[i]:
                got[i].append(logits[i])
    del pools

    w = builder.weights(model)
    notes = hasattr(builder, "reference_logits_and_notes")
    ref_fn = jax.jit(
        (lambda w, ids: builder.reference_logits_and_notes(w, ids, cfg))
        if notes else
        (lambda w, ids: (builder.reference_logits(w, ids, cfg), None)))
    tol = builder.tolerances()["logits"]
    per_row: List[float] = []
    said = []
    longest = max(len(s) for s in seqs)
    for i, n in enumerate(lens):
        # a causal stack: the padding behind a sequence moves none of
        # its rows, and every sequence shares one compiled reference
        padded = np.zeros((longest,), seqs[i].dtype)
        padded[:len(seqs[i])] = seqs[i]
        want, noted = ref_fn(w, jnp.asarray(padded))
        rows = np.asarray(want, np.float32)[n - 1:n + _CHECK_DECODES]
        mine = np.stack(got[i])
        if not np.all(np.isfinite(mine)):
            per_row.append(float("inf"))
            continue
        per_row.extend((np.max(np.abs(mine - rows), axis=-1)
                        / (np.max(np.abs(rows)) + 1e-9)).tolist())
        if notes:
            said.append(builder.reference_report(
                noted, range(n - 1, n + _CHECK_DECODES)))
    worst, mid = max(per_row), harness.median(per_row)
    # the limit is on the worst row; the median and the rows are printed
    # because what moves every row (a precision, a left-out mechanism)
    # and what moves a row or two (a flipped expert selection) read
    # differently there
    harness.check(worst <= tol,
                  f"logits of prefill {lens} in chunks of {chunk} and "
                  f"{_CHECK_DECODES} decode steps through the ragged step "
                  f"against the float32 reference: max error {worst:.2e} "
                  f"of the largest logit, tolerance {tol}; median over "
                  f"the {len(per_row)} checked rows {mid:.2e}; rows "
                  f"{[float(f'{e:.1e}') for e in per_row]}"
                  + "".join(f"; {s}" for s in said), failures)


def chunk_buckets(prompt_lens, chunk: int) -> List[int]:
    """The power-of-two widths of the prefill steps that prompts of
    ``prompt_lens`` reach when a step feeds at most ``chunk`` tokens of
    a prompt (0: the whole prompt)."""
    out = set()
    for n in prompt_lens:
        if chunk and n > chunk:
            out.add(_bucket(chunk))
            n = n % chunk
        if n:
            out.add(_bucket(n))
    return sorted(out)


def _start_engine(cell, args, clock, failures, n_requests: int):
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
    chunk = int(s.get("max_prefill_chunk", 0))
    engine = ServingEngine(model, max_batch=s["max_batch"],
                           page_size=s["page_size"],
                           num_pages=s["num_pages"], dtype=s["dtype"],
                           max_prefill_chunk=chunk,
                           prefix_caching=bool(s.get("prefix_caching",
                                                     True)))
    engine.start()
    # warm exactly the programs this mix can reach: one request per
    # chunk width (two tokens each, so the decode-only program runs too)
    vocab = int(cfg["vocab_size"])
    rs = generator.rng_for(args.seed, 5)
    limit = int(cfg["max_position_embeddings"]) - 4
    lens = generator.Requests(mix, vocab, args.seed, n_requests).prompt_len
    for q in chunk_buckets(lens, chunk):
        t = time.perf_counter()
        out = engine.generate(rs.randint(0, vocab, (min(q, limit),)).tolist(),
                              max_new_tokens=2)
        clock.mark(f"warmed Q={q}: {time.perf_counter() - t:.2f} s, "
                   f"{len(out)} tokens")
    return engine, vocab


def run(cell: Dict[str, Any], args, clock: harness.SetupClock) -> str:
    device = harness.require_device(cell["chips"], args.rehearse)
    compiles = harness.CompileCounter()
    cfg, mix = cell["config"], cell["traffic"]
    failures: List[str] = []
    if mix["loop"] == "open" and "rate" not in cell:
        raise harness.BenchmarkError(
            f"cells/{cell['name']}.json needs a fixed 'rate' for an open "
            f"loop")
    n_requests = int(mix["pool"]) if mix["loop"] == "closed" else len(
        generator.due_times(cell["rate"], float(args.seconds), args.seed))
    engine, vocab = _start_engine(cell, args, clock, failures, n_requests)
    if args.sweep:
        serve._sweep(engine, cell, args, vocab)
        engine.stop(drain=False)
        return ""

    profiler = harness.Profiler(args.out) if args.trace else None
    programs_before = engine.stats()["programs"]
    compiled_before = compiles.count
    clock.window_starts()
    w = serve._window(engine, cell, args.seed, float(args.seconds),
                      cell.get("rate"), vocab, profiler)
    in_window = compiles.count - compiled_before
    stats = w["stats"]
    engine.stop(drain=False)
    serve._join(w["threads"], time.perf_counter() + 10.0)

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
        from benchmark.layer_metrics import experts_window
        steps = [e for e in read_events(os.path.join(args.out, "events"),
                                        kinds=["batch_step"])
                 if not e.get("cold_start")
                 and w["wall"][0] <= e["ts"] <= w["wall"][1]]
        observed: Dict[str, Any] = {
            "batch_steps": steps, "max_batch": cfg["serve"]["max_batch"],
            "gen_late_s": w["late_s"], "tpot_ms": w["tpot_ms"],
            "config": cfg, "device_kind": device["kind"],
            # the stretch the profiler covered, as serve._window times it
            "traced_wall": (
                w["wall"][0] + min(serve._TRACE_FROM_S, w["seconds"] / 4.0),
                w["wall"][0] + min(serve._TRACE_FROM_S, w["seconds"] / 4.0)
                + min(serve._TRACE_FOR_S, w["seconds"] / 2.0))}
        print(f"trace: {len(steps)} batch_step events in the window",
              flush=True)
        device.update(harness.traced_device(profiler, observed,
                                            args.rehearse))
        experts_window.observe_kernels(profiler.newest_xplane(), observed)
        layer = harness.read_layer_metrics(cell["traffic_name"], observed)
        breakdown = harness.breakdown_of(observed)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    return harness.result_line(
        cell, bool(args.trace), correct=not failures,
        attempted=w["attempted"], failed=w["failed"], metrics=metrics,
        layer_metrics=layer, device=device, breakdown=breakdown)
