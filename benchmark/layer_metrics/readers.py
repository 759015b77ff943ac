"""The readers of the per-layer metrics this benchmark ships, and the
reduction from a profiler trace to busy time, top operations and idle
gaps.

A reader takes ``observed`` — what the traced run collected, a dict the
runner fills — and returns the metric's value, or None where there is
nothing to read (the harness then leaves the metric out).  A metric's
file under ``layer_metrics/`` names its reader as ``"module:function"``;
a later metric that reads a new span brings a module of its own.

Keys of ``observed`` (each present only where the runner has it):

* ``step_s``: host-clock seconds from each loss's arrival to the next
  over the window's train steps, those the profiler touched left out;
* ``tokens_per_step``, ``flops_per_token``, ``peak_flops``: for MFU;
* ``batch_steps``: the engine's ``batch_step`` events of the window with
  ``cold_start`` unset; ``max_batch``: the engine's lanes;
* ``gen_late_s``: send time minus due time of each request sent;
* ``tpot_ms``: time per output token of each completed request;
* ``trace``: the dict :func:`reduce_trace` returns.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmark import harness

Event = Tuple[str, int, int]          # name, start_ns, duration_ns

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

def train_step_ms(observed: Dict[str, Any]) -> Optional[float]:
    steps = observed.get("step_s")
    return harness.median(steps) * 1e3 if steps else None


def train_mfu_pct(observed: Dict[str, Any]) -> Optional[float]:
    """Required operations per token x tokens a second (from the median
    step) over the chip's published bf16 peak."""
    steps = observed.get("step_s")
    if not steps or not observed.get("peak_flops"):
        return None
    tokens_per_s = observed["tokens_per_step"] / harness.median(steps)
    return 100.0 * observed["flops_per_token"] * tokens_per_s \
        / observed["peak_flops"]


def padded_rows_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = observed.get("batch_steps")
    if not steps:
        return None
    return harness.padded_rows_pct(steps, observed["max_batch"])


def _step_ms(observed: Dict[str, Any], prefill: bool) -> Optional[float]:
    picked = [s["step_s"] for s in observed.get("batch_steps") or ()
              if (s["prefill_seqs"] > 0) == prefill]
    return harness.median(picked) * 1e3 if picked else None


def prefill_step_ms(observed: Dict[str, Any]) -> Optional[float]:
    """Median ``step_s`` of steps that carried at least one prefill."""
    return _step_ms(observed, True)


def decode_step_ms(observed: Dict[str, Any]) -> Optional[float]:
    """Median ``step_s`` of decode-only steps."""
    return _step_ms(observed, False)


def tpot_p90_ms(observed: Dict[str, Any]) -> Optional[float]:
    """90th percentile over requests of the time per output token."""
    tpot = observed.get("tpot_ms")
    return harness.percentile(tpot, 90.0) if tpot else None


def gen_late_p99_ms(observed: Dict[str, Any]) -> Optional[float]:
    late = observed.get("gen_late_s")
    return harness.percentile(late, 99.0) * 1e3 if late else None


def device_idle_pct(observed: Dict[str, Any]) -> Optional[float]:
    t = observed.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def read_xplane(path: str) -> Dict[str, Any]:
    """Every plane's and line's name, the operations of each device
    plane (its ``XLA Ops`` line) and the host planes' events, from an
    ``.xplane.pb`` as ``jax.profiler`` writes it.  Needs no chip."""
    from jax.profiler import ProfileData
    return planes_of(ProfileData.from_file(path))


def planes_of(data) -> Dict[str, Any]:
    names: List[Tuple[str, List[Tuple[str, int]]]] = []
    device: Dict[str, List[Event]] = {}
    host: List[Tuple[str, Event]] = []
    for plane in data.planes:
        is_device = bool(re.match(DEVICE_PLANE, plane.name))
        is_host = plane.name.startswith("/host:")
        counts = []
        for ln in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in ln.events] \
                if is_host or (is_device and ln.name == OPS_LINE) else None
            counts.append((ln.name, sum(1 for _ in ln.events)
                           if events is None else len(events)))
            if events is not None and is_device:
                device[plane.name] = events
            elif events is not None:
                host.extend((ln.name, ev) for ev in events)
        names.append((plane.name, counts))
    return {"names": names, "device": device, "host": host}


_HLO = re.compile(r"^%?(?P<name>[\w\-]+?)(?:\.\d+)? = \(?(?P<shape>\w+\[[\d,]*\])")


def op_key(name: str) -> str:
    """A trace's operation names are whole HLO instructions, each with a
    number of its own.  The key keeps what repeats from layer to layer
    and step to step: the instruction's name without its number, its
    opcode (and a fusion's kind or a custom call's target) and its first
    output shape — ``fusion kOutput bf16[8192,6144]``,
    ``program custom-call tpu_custom_call f32[8,32,1024,128]``.  A name
    that is no HLO instruction loses only its trailing number."""
    m = _HLO.match(name)
    if not m:
        return re.sub(r"\.\d+$", "", name)[:120]
    parts = [m["name"]]
    op = re.search(r" ([a-z][a-z\-]*)\(", name[m.end():])
    if op and op[1] != m["name"]:
        parts.append(op[1])
    extra = re.search(r'kind=(\w+)|custom_call_target="([^"]+)"', name)
    if extra:
        parts.append(extra[1] or extra[2])
    parts.append(m["shape"])
    return " ".join(parts)[:120]


def _union(events: Sequence[Event]) -> List[List[int]]:
    """Merged ``[start, end]`` intervals of the events, in order."""
    merged: List[List[int]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def _host_name(gap: Sequence[int], host: Sequence[Tuple[str, Event]],
               span_ns: int) -> str:
    """What the host was doing in a gap: the host event that overlaps it
    most, leaving out events longer than half the traced window (a
    thread's outermost frame says nothing)."""
    best, best_ns = "unattributed", 0
    for _, (name, start, dur) in host:
        if dur > span_ns // 2:
            continue
        over = min(gap[1], start + dur) - max(gap[0], start)
        if over > best_ns:
            best, best_ns = name, over
    return best


# idle gaps that are named by the host's events (the longest ones; the
# rest go under "shorter gaps")
_NAMED_GAPS = 200


def reduce_trace(planes: Dict[str, Any], top: int = 10
                 ) -> Optional[Dict[str, Any]]:
    """Busy seconds (union of the intervals in which an operation ran on
    the device, averaged over the device planes), the traced window
    (first operation's start to last operation's end), the operations
    that took most time (summed by :func:`op_key`) and the idle time by
    what the host was doing in the gap (the ``_NAMED_GAPS`` longest gaps
    of each device, summed by the host event that overlaps each most).
    None where no device plane holds an operation."""
    per_device = [ev for ev in planes["device"].values() if ev]
    if not per_device:
        return None
    busy_ns = window_ns = 0
    by_op: Dict[str, int] = {}
    by_host: Dict[str, int] = {}
    for events in per_device:
        merged = _union(events)
        span = merged[-1][1] - merged[0][0]
        window_ns += span
        busy_ns += sum(b - a for a, b in merged)
        for name, _, dur in events:
            key = op_key(name)
            by_op[key] = by_op.get(key, 0) + dur
        gaps = sorted(((merged[i + 1][0] - merged[i][1], i)
                       for i in range(len(merged) - 1)), reverse=True)
        for length, i in gaps[:_NAMED_GAPS]:
            who = _host_name((merged[i][1], merged[i + 1][0]),
                             planes["host"], span)
            by_host[who] = by_host.get(who, 0) + length
        rest = sum(length for length, _ in gaps[_NAMED_GAPS:])
        if rest:
            by_host["shorter gaps"] = by_host.get("shorter gaps", 0) + rest
    n = len(per_device)

    def ranked(d: Dict[str, int]) -> List[List[Any]]:
        return [[k, ns / n / 1e9] for k, ns in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_ns / n / 1e9, "window_s": window_ns / n / 1e9,
            "device_ops": ranked(by_op), "idle_gaps": ranked(by_host)}
