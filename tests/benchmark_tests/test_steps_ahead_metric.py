"""``steps_ahead_pct`` (``benchmark/layer_metrics/steps_ahead.py``): the
share of a window's steps that the serving loop dispatched while the
step before them was still unread, on hand-made ``batch_step`` records,
and nothing where no record carries ``ahead`` — as the records of a loop
that reads every step before it plans the next do."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import steps_ahead  # noqa: E402


def _step(ahead=None, q_width=1, tokens=8):
    rec = {"kind": "batch_step", "q_width": q_width, "tokens": tokens,
           "rows": 8 if q_width == 1 else q_width + 8,
           "prefill_seqs": int(q_width > 1), "fused_steps": 1}
    if ahead:
        rec["ahead"] = True       # the engine writes it only where true
    return rec


@pytest.mark.parametrize("steps,want", [
    # a steady closed loop: the first step after a drain, then nineteen
    ([_step()] + [_step(True)] * 19, 95.0),
    # prefill and decode steps alike run ahead
    ([_step(True, 1024, 607), _step(True), _step(), _step(True)], 75.0),
    # every step ahead
    ([_step(True)] * 7, 100.0),
    # a loop that drained at every other step
    ([_step(True), _step()] * 5, 50.0),
])
def test_share_of_steps_dispatched_behind_an_unread_step(steps, want):
    got = steps_ahead.steps_ahead_pct({"batch_steps": steps})
    assert got == pytest.approx(want)


def test_records_without_the_field_read_as_nothing():
    """The parent commit's records: no ``ahead`` anywhere."""
    old = [_step(), _step(q_width=512, tokens=300)]
    assert steps_ahead.steps_ahead_pct({"batch_steps": old}) is None
    assert steps_ahead.steps_ahead_pct({"batch_steps": []}) is None
    assert steps_ahead.steps_ahead_pct({}) is None
    # a false or null field is no step ahead either
    odd = [dict(_step(), ahead=False), dict(_step(), ahead=None)]
    assert steps_ahead.steps_ahead_pct({"batch_steps": odd}) is None
    # a log that changes hands mid-window: the share of all its steps
    mixed = old + [_step(True)] * 2
    assert steps_ahead.steps_ahead_pct({"batch_steps": mixed}) \
        == pytest.approx(50.0)


def test_the_reader_resolves_and_the_engines_records_carry_the_field():
    """``layer_metrics.steps_ahead:steps_ahead_pct`` is what a metric
    file's ``reader`` would name, and ``ahead`` is in the event schema
    and the docs' table, so a record that carries it is a documented
    one."""
    assert harness.resolve("layer_metrics.steps_ahead:steps_ahead_pct") \
        is steps_ahead.steps_ahead_pct
    from paddle_tpu.observability import events
    assert events.EVENT_SCHEMA["batch_step"]["ahead"] == "bool"
    with open(os.path.join(ROOT, "docs", "observability_events.md"),
              encoding="utf-8") as fh:
        assert "| `ahead` | bool" in fh.read()


def test_no_metric_file_names_it_until_the_manifest_can():
    """``BENCHMARK.json`` does not declare ``steps_ahead_pct.*`` (an
    accepted test pins ``per_layer``'s tail), so no metric file brings
    it into a cell's traced line either: a line never holds a metric the
    manifest does not have."""
    declared = {m["name"] for m in harness.load_manifest()["per_layer"]}
    for mix in ("batch", "longgen"):
        in_line = set(harness.layer_metrics_for(mix))
        assert in_line <= declared, in_line - declared
        assert not any(n.startswith("steps_ahead_pct") for n in in_line)


@pytest.mark.parametrize("cell", ["mistral-7b-8l.batch",
                                  "mimo-v2.5-7l-ep32.longgen"])
def test_a_rehearsed_serve_cell_runs_nine_steps_in_ten_ahead(cell, tmp_path):
    """Both serve cells, end to end on the CPU at their files' tiny
    sizes with the event log on: the reader over the window's warm
    records, as the runner gathers them, finds the loop ahead in nine
    steps of ten and more, the engine's own counters agree, and every
    declared metric of the cell is still in the traced line."""
    import json
    import subprocess
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "3000000030", "--seconds", "3", "--trace", "1",
         "--rehearse", "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    from paddle_tpu.observability import read_events
    steps = [e for e in read_events(str(tmp_path / "events"),
                                    kinds=["batch_step"])
             if not e.get("cold_start")]
    share = steps_ahead.steps_ahead_pct({"batch_steps": steps})
    assert share is not None and share >= 90.0, share
    assert all(s["step_s"] > 0 for s in steps)
    declared = {m["name"] for m in harness.load_manifest()["per_layer"]
                if cell in m["workloads"]}
    assert declared == set(line["metrics"])
