"""The four per-layer metrics that read the serving loop's phase clock
(``benchmark/layer_metrics/engine_phases.py``) on hand-made records, and
the trace reduction's attribution of an idle gap when an ``engine:*``
annotation and the tail of jax's own host-read event overlap it."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import engine_phases, readers  # noqa: E402


def _step(prefill_seqs, plan_s, dispatch_s, host_gap_s=None, queue=None):
    rec = {"kind": "batch_step", "prefill_seqs": prefill_seqs,
           "plan_s": plan_s, "dispatch_s": dispatch_s}
    if host_gap_s is not None:
        rec["host_gap_s"] = host_gap_s
    if queue is not None:
        rec["admit_queue_s"] = queue
    return rec


_STEPS = [
    _step(1, 0.0100, 0.300, queue=[0.5, 1.5]),          # first: no gap yet
    _step(0, 0.0010, 0.002, host_gap_s=0.008),
    _step(0, 0.0030, 0.004, host_gap_s=0.006),
    _step(2, 0.0200, 0.250, host_gap_s=0.090, queue=[2.5]),
    _step(0, 0.0020, 0.003, host_gap_s=0.010),
]


@pytest.mark.parametrize("reader,want", [
    # decode-only steps: 8, 6, 10 ms -> 8; 2, 4, 3 ms -> 3
    (engine_phases.host_gap_ms, 8.0),
    (engine_phases.dispatch_ms, 3.0),
    # all warm steps: 10, 1, 3, 20, 2 ms -> 3
    (engine_phases.sched_plan_ms, 3.0),
    # 0.5, 1.5, 2.5 s: the 90th percentile lies 0.8 of the way from the
    # second to the third sample
    (engine_phases.queue_wait_p90_ms, 2300.0),
])
def test_reader_on_hand_made_records(reader, want, capsys):
    assert reader({"batch_steps": _STEPS}) == pytest.approx(want)
    if reader is engine_phases.queue_wait_p90_ms:
        assert "3 admitted requests" in capsys.readouterr().out


@pytest.mark.parametrize("fn", ["host_gap_ms", "dispatch_ms",
                                "sched_plan_ms", "queue_wait_p90_ms"])
def test_reader_finds_nothing_in_records_without_the_fields(fn):
    """The parent commit's records: step_s and the counts, no phases."""
    old = [{"kind": "batch_step", "prefill_seqs": 0, "step_s": 0.03,
            "q_width": 1, "tokens": 8}] * 3
    reader = getattr(engine_phases, fn)
    assert reader({"batch_steps": old}) is None
    assert reader({"batch_steps": []}) is None
    assert reader({}) is None


def test_decode_only_filter_leaves_out_a_lone_prefill_gap():
    only_prefill = [_step(1, 0.01, 0.3, host_gap_s=0.09)]
    assert engine_phases.host_gap_ms({"batch_steps": only_prefill}) is None
    assert engine_phases.sched_plan_ms({"batch_steps": only_prefill}) \
        == pytest.approx(10.0)


def test_each_metric_file_names_a_reader_of_this_module():
    specs = harness.layer_metrics_for("batch")
    for name in ("host_gap_ms.batch", "dispatch_ms.batch",
                 "sched_plan_ms.batch", "queue_wait_p90_ms.batch"):
        mod, _, fn = specs[name]["reader"].partition(":")
        assert mod == "layer_metrics.engine_phases"
        assert harness.resolve(specs[name]["reader"]) \
            is getattr(engine_phases, fn)


# an idle gap, [2, 8] us, between two operations of a 20 us trace (an event
# longer than half the trace names no gap); on the host the tail of jax's
# np.asarray event reaches 1.2 us into the gap and an engine:plan
# annotation covers 5 us of it
_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
planes {
  name: "/host:CPU"
  lines { name: "serving-engine-1" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 200000 duration_ps: 3000000 }
    %s }
  event_metadata { key: 1 value { id: 1 name: "np.asarray(jax.Array)" } }
  event_metadata { key: 2 value { id: 2 name: "engine:plan" } }
}
"""
_PLAN_EVENT = "events { metadata_id: 2 offset_ps: 2500000 duration_ps: 5000000 }"


@pytest.mark.parametrize("plan_event,first_row", [
    (_PLAN_EVENT, "engine:plan"),
    ("", "np.asarray(jax.Array)"),      # as the parent commit's trace
], ids=["with_phases", "without"])
def test_a_gap_goes_to_the_phase_that_overlaps_it_most(plan_event,
                                                       first_row):
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE % plan_event))
    r = readers.reduce_trace(readers.planes_of(data))
    assert r["busy_s"] == pytest.approx(14e-6)
    assert r["idle_gaps"] == [[first_row, pytest.approx(6e-6)]]
