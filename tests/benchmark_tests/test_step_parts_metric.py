"""The reader of device time by part (``benchmark/layer_metrics/
step_parts.py``) on a small trace built here, and its twenty-three
metrics' files against the manifest.

The metrics' end-to-end rehearsal is ``test_benchmark_rehearse.py``'s:
it runs every cell traced and expects exactly the metrics whose files
name the cell's mix, so each file added here is run there.  This file
hands the readers what a rehearsal's runner hands them and what a chip
run's does.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness                                   # noqa: E402
from benchmark.layer_metrics import step_parts as sp            # noqa: E402
from paddle_tpu.models.generation import (STEP_PARTS,           # noqa: E402
                                          TRAIN_STEP_PARTS)

MANIFEST = harness.load_manifest()
CELL = {"batch": "mistral-7b-8l.batch",
        "longgen": "mimo-v2.5-7l-ep32.longgen",
        "longdoc": "solar-open2-8l-ep32.longdoc",
        "longctx": "glm-5-5l-ep32.longctx",
        "pretrain-2k": "gpt3-760m.pretrain"}
SERVE = ("batch", "longgen", "longdoc", "longctx")
ATTENTION, ENGINE, EXPERTS = \
    "Ragged attention kernel", "Engine step", "Expert layer"
# metric, mix, layer: ISSUE 35's table
METRICS = [("attn_time_pct", m, ATTENTION) for m in SERVE[:3]] \
    + [("kv_write_time_pct", m, ATTENTION) for m in SERVE] \
    + [("ffn_time_pct", m, ENGINE if m == "batch" else EXPERTS)
       for m in SERVE] \
    + [("head_time_pct", m, ENGINE) for m in SERVE] \
    + [("unscoped_time_pct", m, ENGINE) for m in SERVE] \
    + [(n, "pretrain-2k", "Train step") for n in (
        "train_attn_time_pct", "train_mlp_time_pct",
        "train_optimizer_time_pct", "train_unscoped_time_pct")]


def _event(key, shape, path=None):
    stat = f'stats {{ metadata_id: 2 str_value: "{path}" }} ' if path else ""
    return (f'event_metadata {{ key: {key} value {{ id: {key} name: '
            f'"%fusion.{key} = f32[{shape}] fusion()" {stat}}} }}\n')


# Two runs of the decode-only program (20 us each), one of a wide one
# (60 us) and one of another program; times in picoseconds.  Event 9 is
# a loop over events 10 and 11 and event 15 a span over an operation
# under no part: neither has a path of its own.  Events 8 and 16 are
# leaves without a path: the second run's has the head's product behind
# it, the first run's nothing of its own run
_SERVE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 100 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 100 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 101 offset_ps: 40000000 duration_ps: 60000000 }
    events { metadata_id: 102 offset_ps: 100000000 duration_ps: 5000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 10000000 duration_ps: 6000000 }
    events { metadata_id: 6 offset_ps: 16000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 18000000 duration_ps: 1000000 }
    events { metadata_id: 8 offset_ps: 19000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 21000000 duration_ps: 6000000 }
    events { metadata_id: 16 offset_ps: 27000000 duration_ps: 1000000 }
    events { metadata_id: 15 offset_ps: 28000000 duration_ps: 6000000 }
    events { metadata_id: 13 offset_ps: 29000000 duration_ps: 2000000 }
    events { metadata_id: 6 offset_ps: 34000000 duration_ps: 2000000 }
    events { metadata_id: 9 offset_ps: 40000000 duration_ps: 30000000 }
    events { metadata_id: 10 offset_ps: 42000000 duration_ps: 10000000 }
    events { metadata_id: 11 offset_ps: 52000000 duration_ps: 15000000 }
    events { metadata_id: 12 offset_ps: 70000000 duration_ps: 20000000 }
    events { metadata_id: 13 offset_ps: 90000000 duration_ps: 10000000 }
    events { metadata_id: 14 offset_ps: 100000000 duration_ps: 5000000 } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
""" + "".join([
    _event(1, "8,64", "jit(serve_step_q1)/embed/gather"),
    _event(2, "8,32,128", "jit(serve_step_q1)/attention/qkv_proj/dot_general"),
    _event(3, "4112,128", "jit(serve_step_q1)/attention/kv_write/scatter"),
    _event(4, "8,32,8,128", "jit(serve_step_q1)/attention/attn_launch/"
           "jit(_ragged_call)/ragged_paged_attn/pallas_call"),
    _event(5, "8,256", "jit(serve_step_q1)/feed_forward/dot_general"),
    _event(6, "8,512", "jit(serve_step_q1)/lm_head/dot_general"),
    _event(7, "8", "jit(serve_step_q1)/sample/argmax"),
    _event(8, "65,16,128"),                              # no path at all
    _event(9, "16"),               # a while: no path, as on a TPU
    _event(10, "64,256", "jit(serve_step_q64)/experts/expert_matmul/while/"
           "body/dot_general"),
    _event(11, "64,256", "jit(serve_step_q64)/experts/expert_matmul/while/"
           "body/dot_general;jit(serve_step_q64)/attention/kv_write/pad"),
    _event(12, "72,640", "jit(serve_step_q64)/latent_attention/kv_write/"
           "scatter"),
    _event(13, "72,64", "jit(serve_step_q64)/mul"),      # under no part
    _event(14, "8", "jit(convert_element_type)/convert_element_type"),
    _event(15, "4"),               # a span whose body is under no part
    _event(16, "256,512"),         # a weight fetched ahead: no path
]) + """
  event_metadata { key: 100 value { id: 100 name: "jit_serve_step_q1(11)" } }
  event_metadata { key: 101 value { id: 101 name: "jit_serve_step_q64(12)" } }
  event_metadata { key: 102 value { id: 102 name: "jit_convert_element_type(7)" } }
}
planes { name: "/host:CPU" }
"""

# One run of the train step (100 us)
_TRAIN = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 100 offset_ps: 0 duration_ps: 100000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 30000000 duration_ps: 20000000 }
    events { metadata_id: 5 offset_ps: 50000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 60000000 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 65000000 duration_ps: 15000000 }
    events { metadata_id: 8 offset_ps: 80000000 duration_ps: 10000000 }
    events { metadata_id: 9 offset_ps: 90000000 duration_ps: 6000000 }
    events { metadata_id: 10 offset_ps: 96000000 duration_ps: 4000000 } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
""" + "".join([
    _event(1, "4,64,64", "jit(train_step)/attention/flash_fwd/pallas_call"),
    _event(2, "4,64,64", "jit(train_step)/backward/attention/jvp(flash_fwd)/"
           "pallas_call"),
    _event(3, "4,64,64", "jit(train_step)/backward/attention/"
           "transpose(backward)/attention/jvp(flash_bwd_dkv)/pallas_call"),
    _event(4, "64,256", "jit(train_step)/backward/transpose(jvp(mlp))/"
           "dot_general"),
    _event(5, "64,256", "jit(train_step)/backward/mlp/transpose(jvp())/"
           "dot_general"),
    _event(6, "64", "jit(train_step)/backward/add"),
    _event(7, "512,64", "jit(train_step)/backward/lm_head/"
           "transpose(lm_head)/jvp(ln_bwd)/pallas_call"),
    _event(8, "64,256", "jit(train_step)/optimizer/adamw/pallas_call"),
    _event(9, "64,256", "jit(train_step)/optimizer/cast_params/"
           "convert_element_type"),
    _event(10, "64,256"),
]) + """
  event_metadata { key: 100 value { id: 100 name: "jit_train_step(3)" } }
}
"""


def _serialized(text):
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


def _us(row, kind=None):
    if kind:
        return row[kind] * 1e6
    return (row["narrow"] + row["wide"]) * 1e6


@pytest.mark.parametrize("path,rows", [
    ("jit(serve_step_q1)/attention/attn_launch/jit(_ragged_call)/"
     "ragged_paged_attn/pallas_call",
     ("attention", "attention/attn_launch", "attention/ragged_paged_attn")),
    ("jit(serve_step_q8)/experts/router/dot_general",
     ("experts", "experts/router")),
    ("jit(serve_step_q8)/mul", ("unscoped",)),
    # a trace writes ``<path>:<type>``
    ("jit(serve_step_q8)/experts/expert_matmul/cond:",
     ("experts", "experts/expert_matmul")),
    ("", ("unscoped",)),
    # of a merged operation's paths the first counts
    ("jit(serve_step_q8)/experts/while;jit(serve_step_q8)/attention/"
     "kv_write/pad", ("experts",)),
    # a part is a path component, with or without the wrappers
    ("jit(train_step)/attention/flash_fwd/x",
     ("attention", "attention/flash_fwd")),
    ("jit(train_step)/backward/transpose(jvp(attention))/mul",
     ("attention", "attention/backward")),
    ("jit(train_step)/backward/jvp(attention)/jvp(flash_fwd)/x",
     ("attention", "attention/flash_fwd", "attention/backward")),
    ("jit(train_step)/backward/lm_head/transpose(lm_head)/jvp(ln_bwd)/w",
     ("lm_head", "lm_head/ln_bwd", "lm_head/backward")),
    ("jit(train_step)/backward/add", ("backward",)),
    ("jit(train_step)/optimizer/cast_params/convert_element_type",
     ("optimizer", "optimizer/cast_params")),
    # not a substring: "attention_gate" and "my_attention" name no part
    ("jit(serve_step_q1)/my_attention/attention_gate/mul", ("unscoped",)),
    # the serve vocabulary has no "mlp" and no "backward"
    ("jit(serve_step_q1)/backward/mlp/mul", ("unscoped",)),
])
def test_a_part_is_a_path_component(path, rows):
    assert sp.rows_of(path) == rows


def test_serve_parts_are_unions_split_by_program():
    seen = sp.read_parts(_serialized(_SERVE))
    assert seen["train"] is False
    assert seen["runs"] == {"narrow": 2, "wide": 1}
    rows = seen["rows"]
    assert _us(rows["embed"], "narrow") == pytest.approx(2)
    assert _us(rows["attention"], "narrow") == pytest.approx(9)
    assert _us(rows["attention/kv_write"], "narrow") == pytest.approx(3)
    assert _us(rows["attention/attn_launch"]) == pytest.approx(4)
    assert _us(rows["attention/ragged_paged_attn"]) == pytest.approx(4)
    assert _us(rows["feed_forward"], "narrow") == pytest.approx(12)
    assert rows["feed_forward"]["wide"] == 0.0
    # a leaf without a path is its reader's: the next operation under a
    # part in the same run (the head's product here); the first run's
    # last operation has none behind it and stays unscoped
    assert _us(rows["lm_head"], "narrow") == pytest.approx(2 + 1 + 2)
    assert _us(rows["lm_head/no_path"]) == pytest.approx(1)
    # a while spans its body: 30 us, not 30 + 10 + 15; it has no path
    # and is its body's part, turns and all
    assert _us(rows["experts"], "wide") == pytest.approx(30)
    assert _us(rows["experts/expert_matmul"]) == pytest.approx(30)
    assert _us(rows["latent_attention/kv_write"], "wide") \
        == pytest.approx(20)
    # no path inside a run, a path under no part, a span over such:
    # unscoped
    assert _us(rows["unscoped"], "narrow") == pytest.approx(1 + 6)
    assert _us(rows["unscoped"], "wide") == pytest.approx(10)
    # the parts and unscoped are the programs' busy seconds, and the
    # other program's operation is busy time of no part
    parts = sum(_us(rows[p]) for p in STEP_PARTS if p in rows)
    programs = sum(seen["program_s"].values()) * 1e6
    assert programs == pytest.approx(36 + 60)
    assert _us(rows["unscoped"]) == pytest.approx(programs - parts)
    assert seen["busy_s"] * 1e6 == pytest.approx(programs + 5)
    # the largest operations, each with its part
    assert seen["ops"][0][:2] == ["experts", "fusion f32[16]"]
    text = sp.table(seen, ops=3)
    assert "  kv_write" in text and "(other programs)" in text
    assert "  no_path" in text
    assert text.count("\n  op ") == 3


def test_train_parts_count_forward_recompute_and_backward_together():
    seen = sp.read_parts(_serialized(_TRAIN))
    assert seen["train"] is True
    assert seen["runs"] == {"narrow": 0, "wide": 1}
    rows = seen["rows"]
    assert _us(rows["attention"]) == pytest.approx(30)
    assert _us(rows["attention/backward"]) == pytest.approx(20)
    assert _us(rows["attention/flash_fwd"]) == pytest.approx(20)
    assert _us(rows["attention/flash_bwd_dkv"]) == pytest.approx(10)
    assert _us(rows["mlp"]) == pytest.approx(30)
    assert _us(rows["backward"]) == pytest.approx(5)
    assert _us(rows["lm_head/ln_bwd"]) == pytest.approx(15)
    assert _us(rows["optimizer"]) == pytest.approx(16)
    assert _us(rows["optimizer/cast_params"]) == pytest.approx(6)
    assert _us(rows["unscoped"]) == pytest.approx(4)
    assert sum(_us(rows[p]) for p in TRAIN_STEP_PARTS + ("unscoped",)
               if p in rows) == pytest.approx(100)
    assert "the train step" in sp.table(seen)


def _observed(tmp_path, text, busy_us, **more):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_serialized(text))
    return dict({"device_kind": "TPU v5 lite", "xplane_path": str(path),
                 "trace": {"busy_s": busy_us * 1e-6,
                           "window_s": busy_us * 1e-6}}, **more)


def test_metrics_are_shares_of_the_busy_seconds(tmp_path, capsys):
    obs = _observed(tmp_path, _SERVE, 101)
    assert sp.attn_time_pct(obs) == pytest.approx(100 * 9 / 101)
    assert sp.kv_write_time_pct(obs) == pytest.approx(100 * 23 / 101)
    assert sp.ffn_time_pct(obs) == pytest.approx(100 * 42 / 101)
    assert sp.head_time_pct(obs) == pytest.approx(100 * 8 / 101)
    assert sp.unscoped_time_pct(obs) == pytest.approx(100 * 17 / 101)
    # a serve trace has no train step to read, and the other way round
    assert sp.train_attn_time_pct(obs) is None
    # parsed once, printed once
    out = capsys.readouterr().out
    assert out.count("step parts:") == 1 and "unscoped" in out
    train = _observed(tmp_path, _TRAIN, 100)
    assert sp.train_attn_time_pct(train) == pytest.approx(30)
    assert sp.train_mlp_time_pct(train) == pytest.approx(30)
    assert sp.train_optimizer_time_pct(train) == pytest.approx(16)
    assert sp.train_unscoped_time_pct(train) == pytest.approx(4)
    assert sp.attn_time_pct(train) is None


def test_a_tpu_trace_without_the_program_gives_nothing(tmp_path):
    bare = _SERVE.replace("jit_serve_step_q", "jit_other_q") \
        .replace("jit(serve_step_q", "jit(other_q")
    assert sp.read_parts(_serialized(bare)) is None
    obs = _observed(tmp_path, bare, 101)
    assert all(f(obs) is None for f in (
        sp.attn_time_pct, sp.kv_write_time_pct, sp.ffn_time_pct,
        sp.head_time_pct, sp.unscoped_time_pct, sp.train_attn_time_pct))
    # a part that no operation ran under is not a zero
    lone = _observed(tmp_path, _TRAIN.replace("/mlp/", "/attention/")
                     .replace("jvp(mlp)", "jvp(attention)"), 100)
    assert sp.train_mlp_time_pct(lone) is None
    assert sp.train_attn_time_pct(lone) == pytest.approx(60)
    # no traced stretch at all
    assert sp.attn_time_pct({"device_kind": "TPU v5 lite"}) is None


def test_the_train_runners_trace_is_found_from_the_command_line(
        tmp_path, monkeypatch):
    """``runners/train.py`` sets no ``FLAGS_observability_dir`` and hands
    no path: the newest ``.xplane.pb`` under ``<--out>/trace``, or under
    ``benchmark/run.py``'s default for ``--out``."""
    from paddle_tpu.flags import set_flags
    set_flags({"FLAGS_observability_dir": ""})
    where = tmp_path / "out" / "trace" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    (where / "old.xplane.pb").write_bytes(b"")
    os.utime(where / "old.xplane.pb", (1, 1))
    (where / "vm.xplane.pb").write_bytes(_serialized(_TRAIN))
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", "gpt3-760m.pretrain", "--trace", "1",
        "--out", str(tmp_path / "out")])
    assert sp._trace_path({}) == str(where / "vm.xplane.pb")
    obs = {"device_kind": "TPU v5 lite", "trace": {"busy_s": 100e-6}}
    assert sp.train_mlp_time_pct(obs) == pytest.approx(30)
    monkeypatch.setattr(sys, "argv", ["run.py", f"--out={tmp_path}/out"])
    assert sp._trace_path({}) == str(where / "vm.xplane.pb")
    # the default: benchmark_out/<cell> in the checkout
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "no.such"])
    monkeypatch.setattr(harness.Profiler, "newest_xplane",
                        lambda self: self.dir)
    assert sp._trace_path({}) == os.path.join(
        ROOT, "benchmark_out", "no.such", "trace")
    monkeypatch.setattr(sys, "argv", ["pytest"])
    assert sp._trace_path({}) is None
    # a path the runner hands wins
    assert sp._trace_path({"xplane_path": "given"}) == "given"


def test_the_command_prints_the_table_of_any_trace(tmp_path, capsys):
    path = tmp_path / "dump.xplane.pb"
    path.write_bytes(_serialized(_SERVE))
    assert sp.main([str(path), "--ops", "2"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("step parts: 0.000101 s busy")
    assert "2 runs of the decode-only program and 1 of wider programs" in out
    path.write_bytes(_serialized('planes { name: "/host:CPU" }'))
    assert sp.main([str(path)]) == 1


@pytest.mark.parametrize("name,mix,layer", METRICS,
                         ids=[f"{n}.{m}" for n, m, _ in METRICS])
def test_metric_file_reader_and_manifest_entry_agree(name, mix, layer):
    train = mix == "pretrain-2k"
    full = name if train else f"{name}.{mix}"
    spec = harness.layer_metrics_for(mix)[full]
    reader = harness.resolve(spec["reader"])
    assert reader is getattr(sp, name)
    [entry] = [m for m in MANIFEST["per_layer"] if m["name"] == full]
    assert entry == {
        "name": full, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": layer,
        "moves": "train_tokens_per_s" if train else "serve_tokens_per_s",
        "workloads": [CELL[mix]]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    # the cell reports the end-to-end metric the share moves
    assert entry["moves"] in harness.load_cell(CELL[mix])["end_to_end"]
    # what a rehearsal's runner hands over (no device_kind: the reader
    # asks jax, which is the CPU here): a stand-in that means nothing
    stand_in = 100.0 / len(TRAIN_STEP_PARTS if train else STEP_PARTS)
    rehearsal = {"trace": {"busy_s": 0.2, "window_s": 0.3},
                 "batch_steps": [], "step_s": [0.02]}
    assert reader(rehearsal) == pytest.approx(stand_in)
    assert reader({}) is None


def test_the_part_metrics_are_the_files_the_issue_lists():
    ours = {n for n in os.listdir(os.path.join(
        ROOT, "benchmark", "layer_metrics")) if n.endswith(".json")
        and "step_parts:" in open(os.path.join(
            ROOT, "benchmark", "layer_metrics", n)).read()}
    assert ours == {(n if m == "pretrain-2k" else f"{n}.{m}") + ".json"
                    for n, m, _ in METRICS}
    assert len(METRICS) == 23
    # every latent layer is its own part: no attn_time_pct in longctx
    assert "attn_time_pct.longctx.json" not in ours
