"""Device time by part of the model: which part of a serve step or of a
train step the device's busy seconds went to, read from the scopes the
program opens.

**Which operation is whose.**  Every operation of the engine's programs
(``serve_step_q<Q>``) and of the jitted train step (``train_step``) runs
under one ``jax.named_scope`` of a fixed vocabulary, kept in the program
and imported here (``models/generation.py``: ``STEP_PARTS``,
``TRAIN_STEP_PARTS``), and some parts open the sub-scopes of
:data:`SUB_SCOPES` inside.  A device trace keeps an operation's scopes
in the ``tf_op`` stat of its event's metadata, the path jax wrote
(``jit(serve_step_q1)/attention/attn_launch/ragged_paged_attn/...``):
``layer_metrics/linear_attention.py`` declares the few ``XSpace``
messages that hold it, and this module imports them.  The rules:

* an operation belongs to the program whose run on the ``XLA Modules``
  line holds its start, so an operation the compiler added, which has no
  path, counts too; a trace with no such line falls back on the path's
  first component;
* what the compiler adds without a path — the ``slice-done`` /
  ``copy-done`` of a weight it fetches ahead in pieces, a layout copy —
  it schedules right before what reads it: such a leaf takes the part
  of the next operation under a part in the same run, and shows in the
  sub-row ``no_path`` of that part (in ``longdoc`` the pieces of the
  experts' weights are a seventh of the busy seconds).  ``unscoped`` is
  what is left: an operation whose path names no part, and a pathless
  one with nothing under a part behind it in its run;
* its part is the first component of its path that names one.  A
  component names a part with or without the wrappers of a
  differentiated program: ``attention``, ``jvp(attention)`` and
  ``transpose(jvp(attention))`` are the same part.  Of a merged
  operation's several paths (joined by ``;``) the first counts;
* in a train step ``backward`` is no part of the model: the tape's
  operations run under it, a part's as ``backward/<part>/transpose(
  jvp(..))`` and a recomputed forward's as ``backward/<part>/jvp(..)``,
  and they count for their part (forward, recompute and backward
  together; the sub-row ``backward`` says how much of a part ran
  there).  What the tape adds itself (gradients summed where a tensor
  has two consumers, casts of a leaf's gradient) runs under ``backward``
  alone and is the row ``backward``;
* a sub-scope counts wherever it stands in the path below its part, so
  ``kv_write`` is the softmax-attention layers' write and the latent
  layers' two writes alike; ``attn_launch`` is opened by the launch
  itself and always stands under ``attention``;
* a ``while`` or ``conditional`` event spans the events of its body, so
  a row's seconds are the **union** of its events' intervals, never
  their sum.  On a TPU most such events carry no path of their own:
  one that holds other events counts in the rows that its first and its
  last body event under a part share (:func:`_span_rows`), so that a
  loop's turns, and not only its body's operations, are its part's.  A TPU core runs one operation at a time: sibling parts do
  not overlap, and the parts and ``unscoped`` add up to the programs'
  busy seconds.

**The metrics** are shares of the traced stretch's busy device seconds
(``observed["trace"]["busy_s"]``): ``attn_time_pct`` (``attention``),
``kv_write_time_pct`` (``kv_write``), ``ffn_time_pct`` (``feed_forward``
and ``experts``), ``head_time_pct`` (``embed``, ``lm_head`` and
``sample``), ``unscoped_time_pct``; ``train_attn_time_pct``,
``train_mlp_time_pct``, ``train_optimizer_time_pct`` (``optimizer``,
which holds ``cast_params``) and ``train_unscoped_time_pct``.  On a TPU a
trace without the program's operations gives None.  A CPU rehearsal has
no device plane: the readers then return 100 over the number of parts,
to exercise loading, the manifest and the result line; such a value
means nothing.

The trace is parsed once a traced run (``observed["step_parts"]``) and
the table is printed to the run's log.  The same table for any trace::

    python -m benchmark.layer_metrics.step_parts <file.xplane.pb> [--ops N]

The file is found as ``linear_attention._trace_path`` finds it; the
train runner sets no ``FLAGS_observability_dir`` and hands no path, so
there the newest ``.xplane.pb`` under ``<out>/trace`` is taken,
``<out>`` being ``--out`` of the command line or ``benchmark/run.py``'s
default for it.
"""
from __future__ import annotations

import argparse
import bisect
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness
from benchmark.layer_metrics import linear_attention as la
from benchmark.layer_metrics.readers import (DEVICE_PLANE, OPS_LINE, _union,
                                             op_key)

UNSCOPED, BACKWARD, NO_PATH = "unscoped", "backward", "no_path"
# sub-scopes worth a row of their own, by part (the program opens more:
# ``index_score``, ``index_topk`` ... keep their own readers)
SUB_SCOPES = {
    "attention": ("qkv_proj", "kv_write", "attn_launch", "ragged_paged_attn",
                  "ragged_paged_attn_window", "attention_gate", "attn_out",
                  "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "ln_fwd",
                  "ln_bwd"),
    "linear_attention": ("linear_attn_step", "linear_attn_scan"),
    "latent_attention": ("kv_write", "index_select", "sparse_attention"),
    "experts": ("router", "expert_matmul", "shared_expert"),
    "mlp": ("ln_fwd", "ln_bwd"),
    "lm_head": ("ln_fwd", "ln_bwd"),
    "optimizer": ("adamw", "cast_params"),
}
# a program's name in a tf_op path (``jit(train_step)/...``) and on the
# modules' line (``jit_train_step(<id>)``)
_TRAIN_PROGRAM = re.compile(r"^jit[(_]train_step[()/]")
# ``transpose(jvp(attention))`` -> ``attention``; ``jvp()`` -> nothing
_COMPONENT = re.compile(r"^(?:\w+\()*([\w.\-]*?)\)*$")

Interval = Tuple[int, int]                      # start_ps, end_ps


def _vocabulary() -> Optional[Tuple[Tuple[str, ...], Tuple[str, ...]]]:
    """The serve programs' parts and the train step's, or None of a
    program older than the parts (its trace has nothing to read)."""
    try:
        from paddle_tpu.models.generation import (STEP_PARTS,
                                                  TRAIN_STEP_PARTS)
    except ImportError:
        return None
    return tuple(STEP_PARTS), tuple(TRAIN_STEP_PARTS)


def _kind(name: str) -> Optional[str]:
    """``"narrow"`` (the decode-only program), ``"wide"`` (a wider serve
    program, or the train step) or None, of a module's name or a path."""
    if la._NARROW_PROGRAM.match(name):
        return "narrow"
    if la._PROGRAM.match(name) or _TRAIN_PROGRAM.match(name):
        return "wide"
    return None


def rows_of(path: str) -> Tuple[str, ...]:
    """The rows an operation with this ``tf_op`` path counts in: its
    part (or ``unscoped``) and ``<part>/<sub-scope>`` for each sub-scope
    of :data:`SUB_SCOPES` below it.  The path's first component says
    whose vocabulary holds: the train step's or the serve programs'."""
    serve_parts, train_parts = _vocabulary() or ((), ())
    train = bool(_TRAIN_PROGRAM.match(path))
    # ``<path>:<type>`` in a trace; of a merged operation's paths the first
    names = [m[1] for m in (
        _COMPONENT.match(c) for c in
        path.split(";", 1)[0].rstrip(":").split("/")[1:]) if m]
    parts = train_parts if train else serve_parts
    part = next((n for n in names if n in parts and n != BACKWARD), None)
    under_backward = train and BACKWARD in names
    if part is None:
        return (BACKWARD,) if under_backward else (UNSCOPED,)
    below = names[names.index(part) + 1:]
    rows = [part] + [f"{part}/{s}" for s in SUB_SCOPES.get(part, ())
                     if s in below]
    if under_backward:
        rows.append(f"{part}/{BACKWARD}")
    return tuple(rows)


# body events looked at, from each end, for a span's scopes
_SPAN_LOOK = 64


def _span_rows(rows_at: Sequence[Tuple[str, ...]], first: int, last: int
               ) -> Tuple[str, ...]:
    """The rows of a span without a path of its own — the compiler
    leaves most ``while`` and ``conditional`` operations none — from the
    events ``first .. last`` it holds: those that its first and its last
    body event under a part share."""
    def scoped(indices):
        return next((rows_at[i] for i in indices
                     if rows_at[i][0] != UNSCOPED), None)
    head = scoped(range(first, min(last, first + _SPAN_LOOK) + 1))
    tail = scoped(range(last, max(first, last - _SPAN_LOOK) - 1, -1))
    if head is None or tail is None:
        return (UNSCOPED,)
    return tuple(r for r in head if r in tail) or (UNSCOPED,)


def read_parts(serialized: bytes) -> Optional[Dict[str, Any]]:
    """From a serialized ``XSpace``: seconds (the union of intervals,
    averaged over the device planes) of each row in the decode-only
    program's runs (``"narrow"``) and in the wider programs' or the train
    step's (``"wide"``), the runs of each kind, the programs' and all
    operations' busy seconds, and the operations that took most time by
    row (summed, so a ``while`` counts its body twice there).  None where
    no device plane holds an operation of the programs."""
    space = la._xspace_class()()
    space.ParseFromString(serialized)
    spans: Dict[str, Dict[str, List[Interval]]] = {}
    ops: Dict[Tuple[str, str], int] = {}
    runs = {"narrow": 0, "wide": 0}
    program: Dict[str, List[Interval]] = {"narrow": [], "wide": []}
    busy_ps = planes = 0
    train = False
    for plane in space.planes:
        if not re.match(DEVICE_PLANE, plane.name.decode()):
            continue
        stat_names = {e.key: e.value.name.decode()
                      for e in plane.stat_metadata}
        path, name = {}, {}
        for entry in plane.event_metadata:
            name[entry.key] = entry.value.name.decode(errors="replace")
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    # a string stat holds its value, or names a stat
                    # metadata whose name is the value
                    path[entry.key] = \
                        stat.str_value.decode(errors="replace") \
                        or stat_names.get(stat.ref_value, "")
        lines = {ln.name.decode(): ln for ln in plane.lines}
        if OPS_LINE not in lines or not lines[OPS_LINE].events:
            continue
        planes += 1
        # the programs' runs, in order of their start
        modules = []
        if la.MODULES_LINE in lines:
            ln = lines[la.MODULES_LINE]
            t0 = ln.timestamp_ns * 1000
            for ev in ln.events:
                module = name.get(ev.metadata_id, "")
                kind = _kind(module)
                if kind:
                    runs[kind] += 1
                    train = train or bool(_TRAIN_PROGRAM.match(module))
                    modules.append((t0 + ev.offset_ps,
                                    t0 + ev.offset_ps + ev.duration_ps,
                                    kind))
        modules.sort()
        starts = [m[0] for m in modules]
        ln = lines[OPS_LINE]
        t0 = ln.timestamp_ns * 1000
        # in order of their start, a span before what it holds
        events = sorted(((t0 + ev.offset_ps, ev.duration_ps, ev.metadata_id)
                         for ev in ln.events), key=lambda e: (e[0], -e[1]))
        every = [("", lo, dur) for lo, dur, _ in events]
        of_path = {key: (rows_of(path.get(key, "")), op_key(n))
                   for key, n in name.items()}
        begins = [e[0] for e in events]
        rows_at: List[Tuple[str, ...]] = [()] * len(events)
        # a leaf without a path: the next event under a part, if any
        reader_at: Dict[int, int] = {}
        under_part = None
        for i in range(len(events) - 1, -1, -1):
            lo, dur, key = events[i]
            rows_at[i] = of_path[key][0]
            last = bisect.bisect_left(begins, lo + dur) - 1
            if not path.get(key) and last > i:
                rows_at[i] = _span_rows(rows_at, i + 1, last)
            elif not path.get(key) and under_part is not None:
                reader_at[i] = under_part
            if rows_at[i][0] != UNSCOPED:
                under_part = i

        def run_of(at: int) -> Optional[int]:
            i = bisect.bisect_right(starts, at) - 1
            return i if i >= 0 and at < modules[i][1] else None
        for i, (lo, dur, key) in enumerate(events):
            p, rows = path.get(key, ""), rows_at[i]
            if modules:
                run = run_of(lo)
                kind = None if run is None else modules[run][2]
                if i in reader_at and run is not None \
                        and run_of(events[reader_at[i]][0]) == run:
                    part = rows_at[reader_at[i]][0]
                    rows = (part, f"{part}/{NO_PATH}")
            else:
                kind = _kind(p)
                train = train or bool(_TRAIN_PROGRAM.match(p))
            if kind is None:
                continue
            span = (lo, lo + dur)
            program[kind].append(span)
            for row in rows:
                spans.setdefault(row, {"narrow": [], "wide": []})[kind] \
                    .append(span)
            op = (rows[0], of_path[key][1])
            ops[op] = ops.get(op, 0) + dur
        busy_ps += sum(b - a for a, b in _union(every))
    if not planes or not (program["narrow"] or program["wide"]):
        return None

    def seconds(found: Sequence[Interval]) -> float:
        return la._union_s(list(found)) / planes
    return {
        "train": train, "runs": runs, "busy_s": busy_ps / planes / 1e12,
        "program_s": {k: seconds(v) for k, v in program.items()},
        "rows": {row: {k: seconds(v) for k, v in by_kind.items()}
                 for row, by_kind in spans.items()},
        "ops": [[row, key, ps / planes / 1e12] for (row, key), ps in
                sorted(ops.items(), key=lambda kv: -kv[1])]}


def table(seen: Dict[str, Any], busy_s: Optional[float] = None,
          ops: int = 0) -> str:
    """The parts by rows (a sub-scope indented under its part); seconds,
    share of the busy seconds, ms a run of the decode-only program and
    ms a run of the wider programs (of the train step) by columns."""
    serve_parts, train_parts = _vocabulary() or ((), ())
    parts = train_parts if seen["train"] else serve_parts
    busy = busy_s or seen["busy_s"]
    runs = seen["runs"]
    programs = sum(seen["program_s"].values())
    out = [f"step parts: {busy:.6f} s busy, {programs:.6f} s in "
           f"{runs['narrow']} runs of the decode-only program and "
           f"{runs['wide']} of "
           + ("the train step" if seen["train"] else "wider programs"),
           f"{'part':<34}{'seconds':>11}{'% busy':>9}{'ms/narrow':>11}"
           f"{'ms/wide':>11}"]

    def line(label: str, narrow: float, wide: float) -> str:
        per = [f"{1e3 * s / runs[k]:>11.4f}" if runs[k] else f"{'-':>11}"
               for k, s in (("narrow", narrow), ("wide", wide))]
        return (f"{label:<34}{narrow + wide:>11.6f}"
                f"{100 * (narrow + wide) / busy:>9.3f}" + "".join(per))
    rows = seen["rows"]
    for part in parts + (UNSCOPED,):
        if part not in rows:
            continue
        out.append(line(part, rows[part]["narrow"], rows[part]["wide"]))
        for sub in SUB_SCOPES.get(part, ()) + (BACKWARD, NO_PATH):
            r = rows.get(f"{part}/{sub}")
            if r:
                out.append(line("  " + sub, r["narrow"], r["wide"]))
    rest = max(busy - programs, 0.0)
    out.append(f"{'(other programs)':<34}{rest:>11.6f}"
               f"{100 * rest / busy:>9.3f}")
    for row, key, s in seen["ops"][:ops]:
        out.append(f"  op {s:>10.6f} s  {row:<18} {key}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# what a traced run hands over
# ---------------------------------------------------------------------------

def _trace_path(observed: Dict[str, Any]) -> Optional[str]:
    found = la._trace_path(observed)
    if found:
        return found
    # the train runner: ``--out`` as ``benchmark/run.py`` reads it
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--out")
    ap.add_argument("--workload")
    given, _ = ap.parse_known_args(sys.argv[1:])
    out = given.out
    if out is None and given.workload is not None:
        out = os.path.join(os.path.dirname(harness.BENCH_DIR),
                           "benchmark_out", given.workload)
    if out is None:
        return None
    return harness.Profiler(os.path.abspath(out)).newest_xplane()


def _on_chip(observed: Dict[str, Any]) -> bool:
    kind = observed.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    return str(kind).startswith("TPU")


def _observe(observed: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace's parts, read once a traced run (kept under
    ``observed["step_parts"]``) and printed."""
    if "step_parts" not in observed:
        path = _trace_path(observed)
        seen = None
        if path:
            t0 = time.perf_counter()
            with open(path, "rb") as fh:
                seen = read_parts(fh.read())
            print(f"trace: step parts read from {os.path.getsize(path)} "
                  f"bytes of {path} in {time.perf_counter() - t0:.2f} s",
                  flush=True)
        if seen:
            print(table(seen, observed["trace"]["busy_s"], ops=24),
                  flush=True)
        observed["step_parts"] = seen
    return observed["step_parts"]


def _share_pct(observed: Dict[str, Any], rows: Sequence[str],
               train: bool) -> Optional[float]:
    vocabulary = _vocabulary()
    if not observed.get("trace") or vocabulary is None:
        return None
    if not _on_chip(observed):
        # a rehearsal on the CPU: no device plane to read a scope from
        return 100.0 / len(vocabulary[1 if train else 0])
    seen = _observe(observed)
    if not seen or seen["train"] != train:
        return None
    found = [seen["rows"][r] for r in rows if r in seen["rows"]]
    if not found and UNSCOPED not in rows:
        return None
    return 100.0 * sum(r["narrow"] + r["wide"] for r in found) \
        / observed["trace"]["busy_s"]


def attn_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("attention",), train=False)


def kv_write_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("attention/kv_write",
                                 "latent_attention/kv_write"), train=False)


def ffn_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("feed_forward", "experts"), train=False)


def head_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("embed", "lm_head", "sample"), train=False)


def unscoped_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, (UNSCOPED,), train=False)


def train_attn_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("attention",), train=True)


def train_mlp_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("mlp",), train=True)


def train_optimizer_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, ("optimizer", "cast_params"), train=True)


def train_unscoped_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _share_pct(observed, (UNSCOPED,), train=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Device seconds by part of the model, from a trace")
    ap.add_argument("xplane", help="an .xplane.pb as jax.profiler wrote it")
    ap.add_argument("--ops", type=int, default=0,
                    help="also list the N operations that took most time, "
                         "each with its part")
    args = ap.parse_args(argv)
    with open(args.xplane, "rb") as fh:
        seen = read_parts(fh.read())
    if seen is None:
        print("no device plane holds an operation of serve_step_q<Q> or "
              "train_step", file=sys.stderr)
        return 1
    print(table(seen, ops=args.ops))
    return 0


if __name__ == "__main__":
    sys.exit(main())
