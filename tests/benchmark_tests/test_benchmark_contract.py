"""The benchmark's own files against the driver's contract, and the
arithmetic the yardstick rests on, on hand-made samples.

No TPU library is loaded while this module is imported: ``benchmark``
imports jax and the program only inside functions.
"""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, generator, harness  # noqa: E402
from benchmark.layer_metrics import readers      # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert all(_line(w) for w in MANIFEST["command"])
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark_tests"]
    assert len(json.dumps(MANIFEST)) < 64 << 10
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_lines_use_only_what_the_driver_accepts():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert _line(m["layer"])
    for c in MANIFEST["configs"]:
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for base in MANIFEST["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, base)):
            if "__pycache__" in folder:
                continue
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_manifest_agrees_with_the_files_it_names():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    for w in MANIFEST["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config_name"], cell["traffic_name"], cell["chips"],
                cell["why"]) == (w["config"], w["traffic"], w["chips"],
                                 w["why"])
        used.add(w["config"])
        assert "setup_s" in cell["end_to_end"] \
            and len(cell["end_to_end"]) >= 2
    assert used == set(configs)             # each used by some cell
    for name, c in configs.items():
        assert c["file"] == f"benchmark/configs/{name}.json"
        with open(os.path.join(ROOT, c["file"])) as fh:
            data = json.load(fh)
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        # no width may be reduced
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden_size|"
                                 r"intermediate_size|head)", key), key


def test_every_metric_is_reported_where_the_manifest_says():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    reports = {w: set(harness.load_cell(w)["end_to_end"]) for w in CELLS}
    for name, m in e2e.items():
        cells = set(m.get("workloads", CELLS))
        assert cells == {w for w in CELLS if name in reports[w]}, name
    traffic = {w["name"]: w["traffic"] for w in MANIFEST["workloads"]}
    seen = set()
    for m in MANIFEST["per_layer"]:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as fh:
            spec = json.load(fh)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        cells = {w for w in CELLS if traffic[w] == spec["traffic"]}
        assert cells and set(m["workloads"]) == cells, m["name"]
        for w in cells:                     # the metric it moves is there
            assert m["moves"] in reports[w], (m["name"], w)
            seen.add(w)
        harness.resolve(spec["reader"])
    assert seen == set(CELLS)               # every cell has a layer metric


def test_an_unknown_key_or_name_is_an_error(tmp_path, monkeypatch):
    with pytest.raises(harness.BenchmarkError, match="no benchmark/cells"):
        harness.load_cell("no-such.cell")
    monkeypatch.setattr(harness, "BENCH_DIR", str(tmp_path))
    os.makedirs(tmp_path / "cells")
    (tmp_path / "cells" / "x.json").write_text(
        '{"config": "a", "traffic": "b", "chips": 1, "why": "w", '
        '"end_to_end": [], "colour": "red"}')
    with pytest.raises(harness.BenchmarkError, match="colour"):
        harness.load_cell("x")


def test_device_table_refuses_an_unknown_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(harness.BenchmarkError, match="not in"):
        harness.peaks_for("TPU v9")


# --- arithmetic on hand-made samples ---------------------------------------

def test_percentile_interpolates_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert harness.percentile(v, 0) == 10.0
    assert harness.percentile(v, 50) == 30.0
    assert harness.percentile(v, 90) == pytest.approx(46.0)
    assert harness.percentile(v, 100) == 50.0
    assert harness.percentile([7.0], 90) == 7.0
    assert harness.percentile([3.0, 1.0], 50) == 2.0   # sorts first
    with pytest.raises(harness.BenchmarkError):
        harness.percentile([], 50)


def test_tpot_is_the_mean_gap_after_the_first_token():
    assert harness.tpot_ms([1.0, 1.1, 1.4]) == pytest.approx(200.0)
    assert harness.tpot_ms([5.0, 5.05]) == pytest.approx(50.0)
    assert harness.tpot_ms([5.0]) is None
    assert harness.tpot_ms([]) is None


def test_padded_rows_counts_every_lane_at_the_widest_bucket():
    steps = [{"q_width": 1024, "tokens": 600},      # one prefill, 8 lanes
             {"q_width": 1, "tokens": 8},           # a full decode step
             {"q_width": 1, "tokens": 4}]           # half the lanes empty
    rows = 8 * 1024 + 8 + 8
    assert harness.padded_rows_pct(steps, 8) == pytest.approx(
        100.0 * (1 - 612 / rows))
    assert harness.padded_rows_pct([], 8) is None
    obs = {"batch_steps": [
        {"q_width": 1, "tokens": 8, "step_s": 0.04, "prefill_seqs": 0},
        {"q_width": 1, "tokens": 8, "step_s": 0.06, "prefill_seqs": 0},
        {"q_width": 512, "tokens": 300, "step_s": 0.2, "prefill_seqs": 1}],
        "max_batch": 8, "gen_late_s": [0.001, 0.002, 0.003]}
    assert readers.decode_step_ms(obs) == pytest.approx(50.0)
    assert readers.prefill_step_ms(obs) == pytest.approx(200.0)
    assert readers.gen_late_p99_ms(obs) == pytest.approx(2.98)
    assert readers.decode_step_ms({}) is None       # nothing to read


def test_flops_match_the_hand_count_for_gpt3_760m():
    """ISSUE 24: 4.54 GFLOP a token from 757 M matmul parameters plus
    0.45 GFLOP of causal attention at 2048."""
    cfg = harness.load_cell("gpt3-760m.pretrain")["config"]
    shape = harness.builder_for(cfg).flops_shape(cfg)
    # 24 x 12 x 1536^2 in the blocks, 50304 x 1536 in the head
    assert flops.matmul_params(shape) == \
        24 * 12 * 1536 ** 2 + 50304 * 1536 == 756_744_192
    assert 6 * flops.matmul_params(shape) / 1e9 == pytest.approx(4.54,
                                                                 abs=5e-3)
    # 24 layers x 3 (fwd + bwd) x 2 products x 2 x (2048 / 2) x 1536
    assert flops.attention_flops_per_token(shape, 2048) == \
        24 * 3 * 2 * 2 * 1024 * 1536
    assert flops.attention_flops_per_token(shape, 2048) / 1e9 == \
        pytest.approx(0.45, abs=5e-3)
    obs = {"step_s": [0.7, 0.69, 0.71], "tokens_per_step": 8192,
           "flops_per_token": flops.train_flops_per_token(shape, 2048),
           "peak_flops": 197e12}
    assert readers.train_step_ms(obs) == pytest.approx(700.0)
    assert readers.train_mfu_pct(obs) == pytest.approx(
        100 * 4.99345e9 * 8192 / 0.7 / 197e12, rel=1e-4)


@pytest.mark.parametrize("in_flight", [1, 3])
def test_train_loop_keeps_steps_in_flight_and_reads_every_loss(in_flight):
    """A stub step: the loop dispatches ahead of its reads by exactly
    ``in_flight``, reads every loss in order before it returns, and its
    loss-to-loss times add up to the time of the call."""
    import time
    from benchmark.runners import train
    unread, most, read = [], [0], []

    class Loss:
        def __init__(self, i):
            self.i = i
            unread.append(i)
            most[0] = max(most[0], len(unread))

        def __float__(self):
            time.sleep(0.002)
            assert unread.pop(0) == self.i
            read.append(self.i)
            return float(self.i)

    batches = [(i, None) for i in range(4)]
    losses = []
    t0 = time.perf_counter()
    step_s, traced = train._steps(lambda ids, labels: Loss(ids), batches,
                                  2, in_flight, 0.05, losses)
    took = time.perf_counter() - t0
    assert most[0] == in_flight and not unread and not traced
    assert len(step_s) == len(losses) == len(read) >= in_flight
    assert losses[:5] == [2.0, 3.0, 0.0, 1.0, 2.0]      # batches cycled
    assert sum(step_s) == pytest.approx(took, abs=5e-3)
    # seconds = 0: exactly one step, as the warm steps use it
    assert len(train._steps(lambda i, l: Loss(i), batches, 0, 1, 0.0,
                            [])[0]) == 1


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = harness.load_cell("mistral-7b-8l.batch")["traffic"]
    a = generator.Requests(mix, 32768, 1, 32)
    b = generator.Requests(mix, 32768, 3_000_000_019, 32)   # past 2**31
    assert sorted(a.prompt_len) == sorted(b.prompt_len)
    assert sorted(a.output_len) == sorted(b.output_len)
    assert a.prompt_len != b.prompt_len
    assert min(a.prompt_len) >= 128 and max(a.prompt_len) <= 1024
    assert min(a.output_len) >= 16 and max(a.output_len) <= 128
    # the same seed gives the same request; a cycled index new ids
    assert a.get(5) == generator.Requests(mix, 32768, 1, 32).get(5)
    assert len(a.get(5)["prompt"]) == len(a.get(37)["prompt"])
    assert a.get(5)["prompt"] != a.get(37)["prompt"]
    assert generator.prompt_buckets(mix) == [128, 256, 512, 1024]
    due1 = generator.due_times(1.25, 40, 1)
    due2 = generator.due_times(1.25, 40, 2)
    assert len(due1) == len(due2) == 50 and due1[0] == 0.0
    assert max(due1) < 40 and due1 == sorted(due1) and due1 != due2
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip(d, d[1:] + [40]))
    assert gaps(due1) == gaps(due2)


# --- the trace reduction on a small trace built here -----------------------

_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 5100000 duration_ps: 2800000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:loss_read" } }
  event_metadata { key: 2 value { id: 2 name: "outermost" } }
}
"""


def test_trace_reduction_on_a_small_trace():
    from jax.profiler import ProfileData
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    planes = readers.planes_of(data)
    assert [n for n, _ in planes["names"]] == ["/device:TPU:0", "/host:CPU"]
    assert list(planes["device"]) == ["/device:TPU:0"]
    assert len(planes["device"]["/device:TPU:0"]) == 4   # the ops line only
    r = readers.reduce_trace(planes)
    # ops cover [0, 2.5], [4, 5], [8, 9] us of a 9 us window: 4.5 busy
    assert r["busy_s"] == pytest.approx(4.5e-6)
    assert r["window_s"] == pytest.approx(9e-6)
    assert readers.device_idle_pct({"trace": r}) == pytest.approx(50.0)
    # operations are summed by op_key: an instruction without its number
    assert r["device_ops"] == [["fusion", pytest.approx(3e-6)],
                               ["copy", pytest.approx(2e-6)]]
    # idle time by what the host was doing: the gap [5, 8] us falls under
    # the loss read; the event that spans the whole trace names nothing
    assert r["idle_gaps"] == [["bench:loss_read", pytest.approx(3e-6)],
                              ["unattributed", pytest.approx(1.5e-6)]]
    assert readers.reduce_trace({"device": {}, "host": []}) is None
    assert readers.device_idle_pct({}) is None


def test_op_key_keeps_what_repeats_from_layer_to_layer():
    kernel = ('%program.14 = f32[8,32,1024,128]{3,2,1,0:T(8,128)} custom-call('
              's32[8]{0:T(128)S(1)} %copy-done.61, f32[8,2049,16,128]{3,2,1,0} '
              '%bitcast.231), custom_call_target="tpu_custom_call", '
              'frontend_attributes={kernel_metadata={}}')
    assert readers.op_key(kernel) == \
        "program custom-call tpu_custom_call f32[8,32,1024,128]"
    assert readers.op_key(kernel.replace(".14", ".9")) == \
        readers.op_key(kernel)
    fusion = ('%fusion.1682 = (f32[1536]{0:T(1024)}, f32[4,2048,1536]{2,1,0}) '
              'fusion(f32[4,2048]{1,0:T(4,128)S(1)} %reshape.461), '
              'kind=kOutput, calls=%fused_computation.2960')
    assert readers.op_key(fusion) == "fusion kOutput f32[1536]"
    assert readers.op_key("%copy.3 = f32[8,2049,16,128]{3,2,1,0} copy("
                          "f32[8,2049,16,128]{3,2,1,0} %p)") == \
        "copy f32[8,2049,16,128]"
    assert readers.op_key("dot.24") == "dot"


# --- without a chip --------------------------------------------------------

def test_without_a_chip_the_command_fails_and_prints_no_result_line():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs platform 'tpu'" in p.stderr
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
