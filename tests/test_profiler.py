"""paddle.profiler tests (ref test strategy: test/legacy_test profiler
suites — scheduler state machine, RecordEvent spans, summary tables)."""
import json
import os

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import profiler
from paddle_tpu.profiler import (Profiler, ProfilerState, ProfilerTarget,
                                 RecordEvent, SortedKeys, make_scheduler)


def test_make_scheduler_states():
    sched = make_scheduler(closed=1, ready=1, record=2, repeat=1,
                           skip_first=1)
    states = [sched(i) for i in range(7)]
    assert states[0] == ProfilerState.CLOSED          # skip_first
    assert states[1] == ProfilerState.CLOSED          # closed
    assert states[2] == ProfilerState.READY
    assert states[3] == ProfilerState.RECORD
    assert states[4] == ProfilerState.RECORD_AND_RETURN
    assert states[5] == ProfilerState.CLOSED          # repeat exhausted
    assert states[6] == ProfilerState.CLOSED


def test_profiler_records_ops_and_spans(tmp_path):
    exported = []

    def on_ready(prof):
        path = str(tmp_path / "trace.json")
        prof.export(path)
        exported.append(path)

    m = nn.Linear(4, 8)
    x = paddle.to_tensor(np.random.randn(2, 4).astype("float32"))
    p = Profiler(targets=[ProfilerTarget.CPU],
                 scheduler=make_scheduler(closed=0, ready=0, record=2,
                                          repeat=1),
                 on_trace_ready=on_ready)
    p.start()
    for _ in range(2):
        with RecordEvent("fwd"):
            y = m(x)
        p.step()
    p.stop()

    evs = p.events
    names = [e.name for e in evs]
    assert "fwd" in names
    op_events = [e for e in evs
                 if e.type == profiler.TracerEventType.Operator]
    assert op_events, "op dispatch events must be recorded"
    assert any("ProfileStep" in n for n in names)

    assert exported
    trace = json.load(open(exported[0]))
    assert trace["traceEvents"]

    # hook must be uninstalled after stop
    from paddle_tpu.core import dispatch
    assert dispatch._prof_op_hook is None

    s = p.summary(sorted_by=SortedKeys.CPUTotal)
    assert "Operator Summary" in s and "Overview Summary" in s


def test_record_event_outside_profiler_is_noop():
    with RecordEvent("orphan"):
        pass  # must not raise or record


def test_timer_benchmark():
    from paddle_tpu.profiler import benchmark
    bm = benchmark()
    bm.reset()
    bm.begin()
    for _ in range(3):
        bm.step(num_samples=16)
    info = bm.step_info()
    assert "ips" in info
    rep = bm.report()
    assert rep["steps"] == 3


def test_profiler_timer_only():
    p = Profiler(timer_only=True)
    p.start()
    p.step(num_samples=8)
    p.stop()
    assert p.current_state == ProfilerState.CLOSED


def _host_events(trace_dir):
    """(line name, event name, start_ns, end_ns) of every host-plane
    event of the newest .xplane.pb under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = [os.path.join(base, f) for base, _, files in os.walk(trace_dir)
             for f in files if f.endswith(".xplane.pb")]
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [(ln.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for ln in plane.lines for e in ln.events]


def test_spans_reach_a_bare_jax_profiler_trace(tmp_path):
    """No paddle Profiler: a trace started with jax.profiler alone (as
    the benchmark and TensorBoard do) still holds a RecordEvent and the
    serving loop's engine:* phases on its host plane — the phases as
    siblings, none containing another."""
    import jax
    from paddle_tpu.models.gpt import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.engine import _PHASE_NAMES
    paddle.seed(0)
    model = GPTForPretraining(GPTConfig(
        num_layers=1, hidden_size=32, num_heads=2, vocab_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    model.eval()
    engine = ServingEngine(model, max_batch=2, page_size=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with engine:
        engine.generate([1, 2, 3, 4], max_new_tokens=2)      # compile
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with RecordEvent("bare_span"):
                engine.generate([4, 3, 2, 1], max_new_tokens=4)
            # a phase reaches the trace when it CLOSES, and the last
            # step's engine:commit is still open when its request wakes
            # this thread (it closes at the loop's next switch): one
            # more request, whose return proves the loop has moved on,
            # so that the four steps above are whole in the trace
            engine.generate([1], max_new_tokens=1)
        finally:
            jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    assert any(name == "bare_span" for _, name, _, _ in events)
    phases = sorted((e for e in events if e[1].startswith("engine:")),
                    key=lambda e: e[2])
    assert {e[1] for e in phases} <= set(_PHASE_NAMES)
    for name in ("engine:plan", "engine:prepare", "engine:dispatch",
                 "engine:host_read", "engine:commit"):
        assert sum(e[1] == name for e in phases) >= 4, name
    assert len({e[0] for e in phases}) == 1        # one thread's line
    for a, b in zip(phases, phases[1:]):
        assert b[2] >= a[3], (a, b)                # b starts after a ends
