"""Readers of what a model with linear-attention layers adds to the
program: the share of the device's time its mixers take, and the share
of their roofline that the two forms of the gated delta rule reach.

**Which operations are the mixer's.**  The program wraps a
linear-attention layer's mixer in ``jax.named_scope("linear_attention")``
and, inside it, the chunked form in ``linear_attn_scan`` and the
one-token update in ``linear_attn_step`` (``models/generation.py``).  A
device trace keeps an operation's scope, though not in its name: every
event's *metadata* carries the stat ``tf_op``, the operation's path as
jax wrote it (``jit(serve_step_q1)/linear_attention/linear_attn_step/
mul``), which ``jax.profiler.ProfileData`` does not hand out.
:func:`read_scopes` therefore reads the ``.xplane.pb`` itself, with a
few messages of the ``XSpace`` schema declared here (fields that are not
declared are skipped).  The path also names the program, so the
decode-only program's operations are told from a prefill program's, and
the ``XLA Modules`` line counts each program's runs.  A ``while``
operation's event spans the events of its body, so seconds are the
union of the matching events' intervals, never their sum.

**The metrics** (all over the traced stretch):

* ``linear_attn_time_pct``: seconds under ``linear_attention`` over the
  stretch's busy seconds (``observed["trace"]["busy_s"]``);
* ``linear_attn_step_roofline_pct``: over the runs of the decode-only
  program, roofline seconds a step (:func:`step_bytes_ops` of the
  stretch's narrow ``batch_step`` records: the state of every lane that
  fed a row, read and written once a layer) over the seconds a run
  spends under ``linear_attn_step``;
* ``linear_attn_scan_roofline_pct``: over the runs of the wider
  programs, roofline seconds a step (:func:`scan_bytes_ops` of the
  records with ``scan_rows``) over the seconds a run spends under
  ``linear_attn_scan``.

Only what the algorithm cannot avoid is counted, so a share over 100 %
would be a wrong count here and not a fast program.  On a TPU a trace
without such operations gives None.  A rehearsal on the CPU has no
device plane: the shares then divide by ``step_s`` at the v5e's peaks
and the time share is the linear layers' share of the layers, to
exercise the arithmetic; such values mean nothing.

The trace file is ``observed["xplane_path"]`` where a caller gives it;
the runner that is there does not, and the newest ``.xplane.pb`` under
``<out>/trace`` is taken, ``<out>`` being where the runner put the
event log (``FLAGS_observability_dir`` is ``<out>/events``).
"""
from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.layer_metrics import readers

_NARROW_Q = 8
SCOPE, SCAN, STEP = "linear_attention", "linear_attn_scan", \
    "linear_attn_step"
MODULES_LINE = "XLA Modules"
# the engine's programs, and the decode-only one among them, in a tf_op
# path (``jit(serve_step_q1)/...``) and on the modules' line
# (``jit_serve_step_q1(<id>)``)
_PROGRAM = re.compile(r"^jit[(_]serve_step_q\d+[()/]")
_NARROW_PROGRAM = re.compile(r"^jit[(_]serve_step_q1[()/]")


# ---------------------------------------------------------------------------
# what a step had to move and multiply (counted from the configuration)
# ---------------------------------------------------------------------------

def _shape(cfg: Dict[str, Any]) -> Tuple[int, int, int, int]:
    """(linear layers, heads, key width, value width)."""
    lin = cfg["linear_attn_config"]
    n_linear = int(cfg["num_hidden_layers"]) - len(cfg["gqa_layers"])
    return n_linear, int(lin["num_heads"]), int(lin["head_dim"]), \
        int(lin["head_dim"])


def step_bytes_ops(cfg: Dict[str, Any], state_lanes: int
                   ) -> Tuple[float, float]:
    """Bytes and operations of the one-token update of one step: each
    of ``state_lanes`` lanes, in each linear layer, reads its float32
    state ``[heads, dk, dv]`` and writes it back, and multiplies it four
    times (the decay, ``S'^T k``, the outer-product write, ``S^T q``: an
    operation a multiply or an add, so ``7 dk dv`` a head)."""
    layers, heads, dk, dv = _shape(cfg)
    cells = state_lanes * layers * heads * dk * dv
    return float(2 * 4 * cells), float(7 * cells)


def scan_bytes_ops(cfg: Dict[str, Any], scan_rows: int, lanes: int
                   ) -> Tuple[float, float]:
    """Bytes and operations of the chunked form of one step: every one
    of ``scan_rows`` rows, in each linear layer, brings float32 ``q, k,
    g`` of ``dk`` and ``v`` of ``dv`` a head and takes ``o`` of ``dv``
    away, and costs what the recurrence costs a token (``7 dk dv`` a
    head, as :func:`step_bytes_ops`: the blocks' triangles add to that
    and are not counted); each of the ``lanes`` sequences that fed them
    reads and writes its state once a layer."""
    layers, heads, dk, dv = _shape(cfg)
    nbytes = 4 * layers * heads * (scan_rows * (3 * dk + 2 * dv)
                                   + lanes * 2 * dk * dv)
    return float(nbytes), float(7 * scan_rows * layers * heads * dk * dv)


# ---------------------------------------------------------------------------
# what the trace holds
# ---------------------------------------------------------------------------

_XSPACE = None


def _xspace_class():
    """The few messages of ``tsl/profiler/protobuf/xplane.proto`` that
    hold an event's plane, line, time and its metadata's stats, under a
    package of their own (a map field is its entries, repeated)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "paddle_tpu_benchmark_xplane"
    fdp = descriptor_pb2.FileDescriptorProto(
        name=pkg + ".proto", package=pkg, syntax="proto3")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, repeated, type_name in fields:
            m.field.add(
                name=fname, number=number, type=ftype,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL,
                type_name=f".{pkg}.{type_name}" if type_name else None)

    i64, u64, text, sub = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_BYTES, \
        F.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, i64, False, None),
            ("str_value", 5, text, False, None),
            ("ref_value", 7, u64, False, None))
    message("XEventMetadata", ("id", 1, i64, False, None),
            ("name", 2, text, False, None),
            ("stats", 5, sub, True, "XStat"))
    message("XStatMetadata", ("id", 1, i64, False, None),
            ("name", 2, text, False, None))
    message("EventMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, sub, False, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, False, None),
            ("value", 2, sub, False, "XStatMetadata"))
    message("XEvent", ("metadata_id", 1, i64, False, None),
            ("offset_ps", 2, i64, False, None),
            ("duration_ps", 3, i64, False, None))
    message("XLine", ("name", 2, text, False, None),
            ("timestamp_ns", 3, i64, False, None),
            ("events", 4, sub, True, "XEvent"))
    message("XPlane", ("name", 2, text, False, None),
            ("lines", 3, sub, True, "XLine"),
            ("event_metadata", 4, sub, True, "EventMetadataEntry"),
            ("stat_metadata", 5, sub, True, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, sub, True, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName(pkg + ".XSpace"))
    return _XSPACE


Interval = Tuple[int, int]                      # start_ps, end_ps


def read_scopes(serialized: bytes) -> Dict[str, Any]:
    """From a serialized ``XSpace``: for each device plane, the
    intervals (picoseconds) of the ``XLA Ops`` events under ``SCOPE``,
    under ``SCAN`` in the wider programs and under ``STEP`` in the
    decode-only program, and the runs of each kind of program on the
    ``XLA Modules`` line.  ``{"time": [...], "scan": [...], "step":
    [...], "narrow_runs": n, "wide_runs": n}``, intervals and runs summed
    over the device planes."""
    space = _xspace_class()()
    space.ParseFromString(serialized)
    out = {"time": [], "scan": [], "step": [], "narrow_runs": 0,
           "wide_runs": 0}
    for plane in space.planes:
        if not re.match(readers.DEVICE_PLANE, plane.name.decode()):
            continue
        stat_names = {e.key: e.value.name.decode()
                      for e in plane.stat_metadata}
        path, name = {}, {}
        for entry in plane.event_metadata:
            name[entry.key] = entry.value.name.decode(errors="replace")
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) != "tf_op":
                    continue
                # a string stat holds its value, or names a stat
                # metadata whose name is the value
                path[entry.key] = stat.str_value.decode(errors="replace") \
                    or stat_names.get(stat.ref_value, "")
        for line in plane.lines:
            which = line.name.decode()
            if which == MODULES_LINE:
                for ev in line.events:
                    module = name.get(ev.metadata_id, "")
                    if _PROGRAM.match(module):
                        narrow = _NARROW_PROGRAM.match(module)
                        out["narrow_runs" if narrow else "wide_runs"] += 1
            if which != readers.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                p = path.get(ev.metadata_id, "")
                if f"/{SCOPE}/" not in p:
                    continue
                span = (t0 + ev.offset_ps,
                        t0 + ev.offset_ps + ev.duration_ps)
                out["time"].append(span)
                narrow = bool(_NARROW_PROGRAM.match(p))
                if f"/{SCAN}/" in p and not narrow:
                    out["scan"].append(span)
                elif f"/{STEP}/" in p and narrow:
                    out["step"].append(span)
    return out


def _union_s(spans: List[Interval]) -> float:
    """Seconds covered by the intervals (picoseconds)."""
    merged = readers._union([("", lo, hi - lo) for lo, hi in spans])
    return sum(hi - lo for lo, hi in merged) / 1e12


def _trace_path(observed: Dict[str, Any]) -> Optional[str]:
    if observed.get("xplane_path"):
        return observed["xplane_path"]
    from paddle_tpu.flags import get_flag
    events_dir = get_flag("observability_dir")
    if not events_dir:
        return None
    return harness.Profiler(os.path.dirname(str(events_dir))) \
        .newest_xplane()


def _observe(observed: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace's scopes, reduced to seconds and runs, read once a
    traced run (kept under ``observed["linear_attn"]``)."""
    if "linear_attn" not in observed:
        path = _trace_path(observed)
        seen = None
        if path:
            with open(path, "rb") as fh:
                raw = read_scopes(fh.read())
            seen = {"time_s": _union_s(raw["time"]),
                    "scan_s": _union_s(raw["scan"]),
                    "step_s": _union_s(raw["step"]),
                    "narrow_runs": raw["narrow_runs"],
                    "wide_runs": raw["wide_runs"]}
            print(f"trace: linear attention: {seen}", flush=True)
        observed["linear_attn"] = seen
    return observed["linear_attn"]


def _on_chip(observed: Dict[str, Any]) -> bool:
    return str(observed.get("device_kind", "")).startswith("TPU")


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def linear_attn_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    if "config" not in observed or not observed.get("trace"):
        return None
    if not _on_chip(observed):
        layers = _shape(observed["config"])[0]
        return 100.0 * layers / int(observed["config"]["num_hidden_layers"])
    seen = _observe(observed)
    if not seen or not seen["time_s"]:
        return None
    return 100.0 * seen["time_s"] / observed["trace"]["busy_s"]


def _roofline_pct(observed: Dict[str, Any], scan: bool) -> Optional[float]:
    lo, hi = observed.get("traced_wall", (float("-inf"), float("inf")))
    steps = [s for s in observed.get("batch_steps") or ()
             if "scan_rows" in s and lo <= s["ts"] <= hi
             and (s["scan_rows"] > 0 if scan
                  else s["q_width"] <= _NARROW_Q and s["state_lanes"] > 0)]
    if not steps or "config" not in observed:
        return None
    cfg = observed["config"]
    if _on_chip(observed):
        seen = _observe(observed)
        seconds, runs = (("scan_s", "wide_runs") if scan
                         else ("step_s", "narrow_runs"))
        if not seen or not seen[seconds] or not seen[runs]:
            return None
        peaks = harness.peaks_for(observed["device_kind"])
        device_s = seen[seconds] / seen[runs]
    else:
        # a rehearsal on the CPU: no device plane to read a scope from
        peaks = harness.DEVICE_PEAKS["TPU v5 lite"]
        device_s = harness.median([s["step_s"] for s in steps])
    total = 0.0
    for s in steps:
        nbytes, ops = scan_bytes_ops(cfg, s["scan_rows"],
                                     max(s["prefill_seqs"], 1)) if scan \
            else step_bytes_ops(cfg, s["state_lanes"])
        total += max(nbytes / peaks["hbm_bytes_per_s"],
                     ops / peaks["bf16_flops"])
    if not total or not device_s:
        return None
    return 100.0 * (total / len(steps)) / device_s


def linear_attn_step_roofline_pct(observed: Dict[str, Any]
                                  ) -> Optional[float]:
    return _roofline_pct(observed, scan=False)


def linear_attn_scan_roofline_pct(observed: Dict[str, Any]
                                  ) -> Optional[float]:
    return _roofline_pct(observed, scan=True)
