"""Every cell that has a file under ``benchmark/cells/`` — those of
BENCHMARK.json and those kept as data for a later PR — end to end in a
subprocess on the CPU (``--rehearse``: the files' tiny sizes, Pallas in
interpret mode), once untraced and once traced: the last line has exactly
the contract's keys and names the CPU as its device.  Its numbers are not
device numbers.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

MANIFEST = harness.load_manifest()
CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(
    ROOT, "benchmark", "cells")) if f.endswith(".json"))
assert {w["name"] for w in MANIFEST["workloads"]} <= set(CELLS)


def _run(cell, trace, tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "3000000019", "--seconds", "3", "--trace", str(trace),
         "--rehearse", "--out", str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_end_to_end(cell, trace, tmp_path):
    line, out = _run(cell, trace, tmp_path)
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    assert line["correct"] is True, out[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["device"]) == (dev | {"busy_s", "window_s"}
                                   if trace else dev)
    assert line["device"]["platform"] == "cpu"
    files = harness.load_cell(cell)
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    if trace:
        expect = {n: spec["unit"] for n, spec in
                  harness.layer_metrics_for(files["traffic_name"]).items()}
        # no peak for a CPU: a rehearsal leaves the MFU out
        expect.pop("train_mfu_pct", None)
        assert line["device"]["busy_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(len(v) <= 10 for v in line["breakdown"].values())
    else:
        expect = {n: got.get(n) for n in files["end_to_end"]}
    assert got == expect
    # where BENCHMARK.json lists the metric, the unit is the manifest's
    for m in MANIFEST["per_layer" if trace else "end_to_end"]:
        if m["name"] in got:
            assert got[m["name"]] == m["unit"], m["name"]
    assert all(isinstance(v["value"], float) and v["value"] > 0
               for v in line["metrics"].values())
    assert "nothing compiled inside the window" in out
