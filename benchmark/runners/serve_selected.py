"""Runner "serve_selected": runner "serve_described" for a model whose rows
SELECT the keys they attend, so that the logits check has to feed
prompts longer than the selection keeps.

``serve_described``'s check prefills 1,500 and 300 tokens (a module
constant): both under an ``index_topk`` of 2,048, where the selection
keeps every key, so that check could not tell a program with no index
from the model.  This runner's check feeds ``CHECK_PROMPTS`` = 4,500 and
300 tokens: rows past 2,048 of the first choose 2,048 of up to 4,504
keys, in prefill and in the teacher-forced decode steps, and the second
sees every key.  Everything else is ``serve_described``'s and
``serve``'s, imported and not copied wherever their functions take what
they need as arguments (``chunk_buckets``, the window, the result
line's parts); ``check_logits``, ``_start_engine`` and ``run`` call one
another by name in ``serve_described`` and are therefore written out
here, the check with its prompts as an argument.  A ``benchmark`` PR
that folds the runners should take the check's lengths from the builder
(PERF.md section 7, ROADMAP D14).

The comparison reads the checked rows' errors as a set
(:func:`rows_agree`): every row within ``tolerances()["logits"]`` but at
most ``["flipped_rows"]`` of them, which are held to
``["logits_flipped_row"]``, and the median within ``["logits_median"]``
— a key flipped at the selection's edge, and the expert flipped behind
it, move one row by a few per cent and no other
(``benchmark/reference/glm5.py`` says why, with the chip's readings).
What else a configuration's builder has to provide is what
``serve_described`` asks for.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Sequence

import numpy as np

from benchmark import generator, harness
from benchmark.runners import serve
from benchmark.runners.serve_described import _bucket, chunk_buckets

# prompts of the logits check (tokens) and the teacher-forced decode
# steps behind each: the first is more than twice index_topk
CHECK_PROMPTS, CHECK_DECODES = (4500, 300), 4


def step_logits(model, cfg: Dict[str, Any], seed: int,
                prompts: Sequence[int] = CHECK_PROMPTS,
                decodes: int = CHECK_DECODES):
    """Seeded prompts of ``prompts`` tokens through the model's own
    ragged step over fresh pools of the step's own description: prefill
    in chunks of the configuration's ``max_prefill_chunk``, then
    ``decodes`` teacher-forced decode steps.  Returns ``(seqs, lens,
    got)``: each sequence's ids (prompt and forced tokens), its prompt's
    length, and the step's last-row logits of its last prefill chunk and
    of every decode step."""
    import jax
    params, step = model.build_ragged_decode_step()
    cache = step.cache
    step = jax.jit(step)
    c = model.config
    ps = int(cfg["serve"]["page_size"])
    max_pos = int(c.max_position_embeddings)
    chunk = int(cfg["serve"].get("max_prefill_chunk", 0)) or max_pos
    rs = generator.rng_for(seed, 4)
    lens = [min(n, max_pos - decodes - 1) for n in prompts]
    seqs = [rs.randint(0, int(c.vocab_size), (n + decodes,)) for n in lens]
    b = len(seqs)
    ppseq = -(-(max(lens) + decodes) // ps)
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
    while any(d < n + decodes for d, n in zip(done, lens)):
        count = [min(chunk, n - d) if d < n else int(d < n + decodes)
                 for d, n in zip(done, lens)]
        tok, pos, page_ids, slots, kv, ql = feed(done, count)
        out = step(params, tok, pos, pools, page_ids, slots, kv, ql,
                   tables)
        logits, pools = np.asarray(out[0], np.float32), out[1]
        for i in range(b):
            done[i] += count[i]
            if count[i] and done[i] >= lens[i]:
                got[i].append(logits[i])
    return seqs, lens, [np.stack(g) for g in got]


def row_errors(mine: np.ndarray, want: np.ndarray) -> List[float]:
    """Each checked row's largest difference as a share of the largest
    reference logit of the rows (inf where the step's are not finite)."""
    if not np.all(np.isfinite(mine)):
        return [float("inf")]
    return (np.max(np.abs(mine - want), axis=-1)
            / (np.max(np.abs(want)) + 1e-9)).tolist()


def check_logits(model, builder, cfg: Dict[str, Any], seed: int,
                 failures: List[str],
                 prompts: Sequence[int] = CHECK_PROMPTS,
                 decodes: int = CHECK_DECODES) -> None:
    """:func:`step_logits` against the plain reference's full forward
    pass, row by row."""
    import jax
    import jax.numpy as jnp
    seqs, lens, got = step_logits(model, cfg, seed, prompts, decodes)
    chunk = int(cfg["serve"].get("max_prefill_chunk", 0)) \
        or int(model.config.max_position_embeddings)

    w = builder.weights(model)
    ref_fn = jax.jit(
        lambda w, ids: builder.reference_logits_and_notes(w, ids, cfg))
    tols = builder.tolerances()
    per_row: List[float] = []
    said = []
    longest = max(len(s) for s in seqs)
    for i, n in enumerate(lens):
        # a causal stack: the padding behind a sequence moves none of
        # its rows, and every sequence shares one compiled reference
        padded = np.zeros((longest,), seqs[i].dtype)
        padded[:len(seqs[i])] = seqs[i]
        want, noted = ref_fn(w, jnp.asarray(padded))
        rows = np.asarray(want, np.float32)[n - 1:n + decodes]
        per_row.extend(row_errors(got[i], rows))
        said.append(builder.reference_report(noted,
                                             range(n - 1, n + decodes)))
    harness.check(rows_agree(per_row, tols),
                  f"logits of prefill {lens} in chunks of {chunk} and "
                  f"{decodes} decode steps through the ragged step "
                  f"against the float32 reference, as a share of the "
                  f"largest logit: every row within {tols['logits']:g} "
                  f"but at most {tols['flipped_rows']} (a flipped "
                  f"selection: within {tols['logits_flipped_row']:g}), "
                  f"their median within {tols['logits_median']:g}; "
                  f"{sum(not e <= tols['logits'] for e in per_row)} rows "
                  f"over {tols['logits']:g}, max {max(per_row):.2e}, "
                  f"median {harness.median(per_row):.2e}; rows "
                  f"{[float(f'{e:.1e}') for e in per_row]}"
                  + "".join(f"; {s}" for s in said), failures)


def rows_agree(per_row: Sequence[float], tols: Dict[str, float]) -> bool:
    """The comparison that decides ``correct``, over the checked rows'
    errors as a set: what moves every row (a precision, a left-out
    mechanism) fails the median's limit and the rows' own; what moves a
    row or two (a flipped key, then a flipped expert: the model's own
    discontinuity under the step's rounding) is held to what one
    expert's term can move a row by, on at most ``flipped_rows`` rows."""
    over = sum(not e <= tols["logits"] for e in per_row)
    return (over <= tols["flipped_rows"]
            and all(e <= tols["logits_flipped_row"] for e in per_row)
            and harness.median(per_row) <= tols["logits_median"])


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
    if mix["loop"] != "closed":
        raise harness.BenchmarkError(
            f"runner serve_selected drives closed loops; traffic "
            f"{cell['traffic_name']!r} is {mix['loop']!r}")
    engine, vocab = _start_engine(cell, args, clock, failures,
                                  int(mix["pool"]))

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

    layer, breakdown = {}, None
    if args.trace:
        from paddle_tpu.observability import read_events
        steps = [e for e in read_events(os.path.join(args.out, "events"),
                                        kinds=["batch_step"])
                 if not e.get("cold_start")
                 and w["wall"][0] <= e["ts"] <= w["wall"][1]]
        traced_from = w["wall"][0] + min(serve._TRACE_FROM_S,
                                         w["seconds"] / 4.0)
        observed: Dict[str, Any] = {
            "batch_steps": steps, "max_batch": cfg["serve"]["max_batch"],
            "gen_late_s": w["late_s"], "tpot_ms": w["tpot_ms"],
            "config": cfg, "device_kind": device["kind"],
            "xplane_path": None,
            # the stretch the profiler covered, as serve._window times it
            "traced_wall": (traced_from, traced_from + min(
                serve._TRACE_FOR_S, w["seconds"] / 2.0))}
        print(f"trace: {len(steps)} batch_step events in the window",
              flush=True)
        device.update(harness.traced_device(profiler, observed,
                                            args.rehearse))
        observed["xplane_path"] = profiler.newest_xplane()
        layer = harness.read_layer_metrics(cell["traffic_name"], observed)
        breakdown = harness.breakdown_of(observed)
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell["chips"])
    return harness.result_line(
        cell, bool(args.trace), correct=not failures,
        attempted=w["attempted"], failed=w["failed"], metrics=metrics,
        layer_metrics=layer, device=device, breakdown=breakdown)
