"""Profiler — TPU-native re-design of the reference's
``python/paddle/profiler/profiler.py``.

Two tracers, matching the reference's host-tracer + device-tracer split:

- **Host events**: ``RecordEvent`` spans and per-op dispatch events (hooked
  into ``core.dispatch.call_op``) are recorded into an in-process buffer
  with wall-clock begin/end, then exported as chrome-trace JSON and
  aggregated by ``profiler_statistic`` into summary tables.  This replaces
  the reference's native ``RecordEvent``/host_tracer (C++) — on a
  single-controller JAX runtime the host side IS Python, so the honest
  native equivalent is an in-process recorder, not a C++ shim.
- **Device (XPlane) traces**: the real device timeline comes from XLA's
  own profiler.  ``Profiler`` starts/stops ``jax.profiler`` tracing when a
  ``trace_dir`` is given (TensorBoard/perfetto-compatible XPlane dumps),
  and ``RecordEvent`` always doubles as ``jax.profiler.TraceAnnotation``,
  so host spans show up inside the device timeline of any live trace,
  whoever started it — the TraceMe/RecordEvent parity called for in
  SURVEY.md §5.

The scheduler state machine (CLOSED/READY/RECORD/RECORD_AND_RETURN,
``make_scheduler``) and the ``on_trace_ready`` export-handler contract are
kept API-identical to the reference.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax


class ProfilerState(Enum):
    """ref: profiler.ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    """ref: profiler.ProfilerTarget (CPU/GPU/XPU/CUSTOM_DEVICE) — the
    TPU-native build exposes CPU (host) and TPU (device/XPlane)."""
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class TracerEventType(Enum):
    """Subset of the reference's event taxonomy that exists on this
    runtime (ref: paddle/fluid/platform/profiler/trace_event.h)."""
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    UserDefined = 3
    Forward = 4
    Backward = 5
    Optimization = 6
    Communication = 7
    PythonOp = 8


class HostEvent:
    __slots__ = ("name", "type", "start", "end", "tid")

    def __init__(self, name: str, type: TracerEventType, start: float,
                 end: float, tid: int):
        self.name = name
        self.type = type
        self.start = start
        self.end = end
        self.tid = tid

    @property
    def duration(self) -> float:
        return self.end - self.start


class _HostRecorder:
    """Thread-safe host event buffer; active only while a Profiler is in a
    RECORD state."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: List[HostEvent] = []
        self.recording = False

    def clear(self):
        with self._lock:
            self.events = []

    def add(self, name: str, etype: TracerEventType, start: float,
            end: float):
        if not self.recording:
            return
        with self._lock:
            self.events.append(HostEvent(name, etype, start, end,
                                         threading.get_ident()))


_recorder = _HostRecorder()


def _op_profile_hook(op_name: str, start: float, end: float):
    _recorder.add(op_name or "op", TracerEventType.Operator, start, end)


class RecordEvent:
    """User-defined span (ref: profiler.RecordEvent).

    Context manager / begin-end pair.  Every span also enters
    ``jax.profiler.TraceAnnotation``, so it lands on the host plane of
    ANY live profiler trace — paddle_tpu's own ``Profiler``, a bare
    ``jax.profiler.start_trace`` or TensorBoard's capture — on the same
    clock as the device plane.  A TraceMe is itself a no-op while no
    trace is live, so an unobserved span costs one small object and no
    clock read.
    """

    __slots__ = ("name", "event_type", "_t0", "_annotation")

    def __init__(self, name: str,
                 event_type: TracerEventType = TracerEventType.UserDefined):
        self.name = name
        self.event_type = event_type
        self._t0: Optional[float] = None
        self._annotation = None

    def begin(self):
        # only spans fully inside a record window count for the host
        # recorder: a span opened before the window would otherwise be
        # stored with a pre-window start time (inflated duration)
        self._t0 = time.perf_counter() if _recorder.recording else None
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()

    def end(self):
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._t0 is not None and _recorder.recording:
            _recorder.add(self.name, self.event_type, self._t0,
                          time.perf_counter())
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """ref: profiler.make_scheduler — cyclic CLOSED^closed READY^ready
    RECORD^record schedule, last record step returns RECORD_AND_RETURN."""
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("closed/ready must be >=0 and record > 0")
    cycle = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str,
                          worker_name: Optional[str] = None) -> Callable:
    """ref: profiler.export_chrome_tracing — returns an on_trace_ready
    handler that dumps chrome-trace JSON into ``dir_name``."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof: "Profiler"):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time()*1000)}.paddle_trace.json")
        prof.export(path, format="json")

    return handler


def export_protobuf(dir_name: str,
                    worker_name: Optional[str] = None) -> Callable:
    """API-parity alias (ref exports protobuf; here the device-grade dump
    is the XPlane dir written by jax.profiler, so this exports the host
    JSON alongside it)."""
    return export_chrome_tracing(dir_name, worker_name)


class Profiler:
    """ref: profiler.Profiler.

    Args mirror the reference: ``targets`` (ProfilerTarget list),
    ``scheduler`` (callable step->state, a (start, end) tuple, or None for
    always-RECORD), ``on_trace_ready`` handler, ``timer_only`` (just ips
    accounting).  ``trace_dir`` (TPU-native extra): when set and TPU is in
    targets, a jax.profiler XPlane trace is captured over each RECORD
    window for TensorBoard.
    """

    def __init__(self, *, targets: Optional[Sequence[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, trace_dir: Optional[str] = None):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU,
                                                      ProfilerTarget.TPU]
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            raise TypeError(f"bad scheduler: {scheduler!r}")
        self.on_trace_ready = on_trace_ready
        self.record_shapes = record_shapes
        self.profile_memory = profile_memory
        self.timer_only = timer_only
        self.trace_dir = trace_dir
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._events: List[HostEvent] = []
        self._step_t0: Optional[float] = None
        self._xplane_live = False
        self._owns_recorder = False

    # -- recording control -------------------------------------------------
    def _begin_record(self):
        _recorder.clear()
        self._owns_recorder = True
        _recorder.recording = True
        from ..core import dispatch
        dispatch._prof_op_hook = _op_profile_hook
        if (self.trace_dir and ProfilerTarget.TPU in self.targets
                and not self._xplane_live):
            try:
                jax.profiler.start_trace(self.trace_dir)
                self._xplane_live = True
            except Exception:
                self._xplane_live = False

    def _end_record(self):
        from ..core import dispatch
        dispatch._prof_op_hook = None
        _recorder.recording = False
        self._owns_recorder = False
        self._events = list(_recorder.events)
        if self._xplane_live:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._xplane_live = False

    # -- lifecycle (ref: start/stop/step) ----------------------------------
    def start(self):
        from .timer import benchmark
        benchmark().begin()
        if self.timer_only:
            return
        self.current_state = self._scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._begin_record()
        self._step_t0 = time.perf_counter()

    def stop(self):
        from .timer import benchmark
        benchmark().end()
        if self.timer_only:
            return
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            self._end_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
        self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[float] = None):
        from .timer import benchmark
        benchmark().step(num_samples)
        if self.timer_only:
            self.step_num += 1
            return
        if self._step_t0 is not None and _recorder.recording:
            _recorder.add(f"ProfileStep#{self.step_num}",
                          TracerEventType.ProfileStep, self._step_t0,
                          time.perf_counter())
        prev = self.current_state
        self.step_num += 1
        new = self._scheduler(self.step_num)
        recording = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if prev == ProfilerState.RECORD_AND_RETURN:
            self._end_record()
            if self.on_trace_ready:
                self.on_trace_ready(self)
        if new in recording and not _recorder.recording:
            self._begin_record()
        if new not in recording and _recorder.recording:
            self._end_record()
        self.current_state = new
        self._step_t0 = time.perf_counter()

    def step_info(self, unit: str = "samples") -> str:
        from .timer import benchmark
        return benchmark().step_info(unit)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results -----------------------------------------------------------
    @property
    def events(self) -> List[HostEvent]:
        # mid-record: the live buffer is ours; otherwise only what THIS
        # profiler captured (never another profiler's global buffer)
        if self._owns_recorder:
            return list(_recorder.events)
        return list(self._events)

    def export(self, path: str, format: str = "json"):
        """Write the recorded host events as chrome-trace JSON (load in
        chrome://tracing or perfetto)."""
        evs = self.events
        trace = {
            "traceEvents": [
                {
                    "name": e.name, "ph": "X", "pid": os.getpid(),
                    "tid": e.tid, "ts": e.start * 1e6,
                    "dur": e.duration * 1e6,
                    "cat": e.type.name,
                } for e in evs
            ],
            "displayTimeUnit": "ms",
        }
        with open(path, "w") as f:
            json.dump(trace, f)

    def summary(self, sorted_by=None, op_detail: bool = True,
                thread_sep: bool = False, time_unit: str = "ms") -> str:
        from .profiler_statistic import gen_summary
        s = gen_summary(self.events, sorted_by=sorted_by,
                        time_unit=time_unit)
        print(s)
        return s


def load_profiler_result(path: str) -> Dict[str, Any]:
    """Load a chrome-trace JSON written by Profiler.export."""
    with open(path) as f:
        return json.load(f)
