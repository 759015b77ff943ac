"""The benchmark's side of the fourth configuration
(``solar-open2-8l-ep32``): its file against the published values, its
sizes against the file's notes, and the new readers' arithmetic on
hand-made samples and on a small trace built here.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, harness                        # noqa: E402
from benchmark.layer_metrics import experts_every_layer as eel  # noqa: E402
from benchmark.layer_metrics import linear_attention as la      # noqa: E402
from benchmark.runners import serve_described                   # noqa: E402

CELL = "solar-open2-8l-ep32.longdoc"

# https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json,
# the language model's keys as the catalog beside the model-configs
# guide holds them
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def test_the_file_holds_the_published_values_but_for_what_it_reduces():
    cell = harness.load_cell(CELL)
    cfg, mix = cell["config"], cell["traffic"]
    reduced = ["num_hidden_layers", "gqa_layers", "n_routed_experts",
               "vocab_size", "max_position_embeddings", "torch_dtype"]
    assert cfg["reduced"] == reduced
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # the cut: two whole periods, a share of 1/32 of the experts, an
    # eighth of the vocabulary; the file states the published numbers
    assert cfg["num_hidden_layers"] == 8
    assert cfg["gqa_layers"] == PUBLISHED["gqa_layers"][:2] == [0, 4]
    assert cfg["n_routed_experts"] * 32 == PUBLISHED["n_routed_experts"] \
        == cfg["n_router_outputs"]
    assert cfg["n_routed_experts"] >= 8 and cfg["first_held_expert"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    said = " ".join(cfg["assumed"]) + cfg["notes"]
    for number in ("320", "196608", "32 chips", "1048576", "bfloat16"):
        assert number in said, number
    assert [a[:3] for a in cfg["assumed"][:5]] \
        == ["(a)", "(b)", "(c)", "(d)", "(e)"]
    s = cfg["serve"]
    assert s["num_pages"] == s["max_batch"] \
        * cfg["max_position_embeddings"] // s["page_size"] + 1
    assert s["prefix_caching"] is False and s["max_prefill_chunk"] == 1024
    # ISSUE 31's mix: documents in, a few hundred tokens out
    assert mix["prompt"] == {"kind": "lognormal", "median": 3072,
                             "sigma": 0.5, "min": 1536, "max": 6144}
    assert mix["output"] == {"kind": "lognormal", "median": 192,
                             "sigma": 0.5, "min": 96, "max": 384}
    assert (mix["clients"], mix["pool"], mix["loop"]) == (16, 32, "closed")
    assert mix["runner"] == "serve_described"
    # the longest request fits a lane, and eight of them the pool
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert longest <= cfg["max_position_embeddings"]
    assert s["max_batch"] * -(-longest // s["page_size"]) < s["num_pages"]
    lens = generator.lengths(mix["prompt"], mix["pool"])
    assert serve_described.chunk_buckets(lens, 1024) \
        == [64, 128, 256, 512, 1024]
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    # the precision the file states is the description's
    described = harness.builder_for(cfg)._model_config(cfg).description()
    assert described.precision == "high" and "precision high" in cfg["precision"]


def _parameters(cfg):
    """Parameters of the chip's share, from the file's sizes."""
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    nl, rank, k = lin["num_heads"] * lin["head_dim"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    gqa = h * nq + 2 * h * nkv + h * nq + nq * h
    kda = 3 * h * nl + 3 * k * nl + 2 * (h * rank + rank * nl) + nl \
        + lin["num_heads"] + h * lin["num_heads"] + lin["head_dim"] + nl * h
    one = 3 * h * cfg["moe_intermediate_size"]
    ff = h * cfg["n_router_outputs"] + cfg["n_router_outputs"] \
        + (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * one
    total = 2 * cfg["vocab_size"] * h + h
    for i in range(cfg["num_hidden_layers"]):
        total += (gqa if i in cfg["gqa_layers"] else kda) + ff + 2 * h
    return total, gqa, kda, ff


def test_the_parameters_the_pools_and_the_state_are_what_the_notes_say():
    """The count by hand equals the program's at the rehearsal's sizes,
    and gives the notes' 2,640,502,400 at the published ones; the pools
    hold every position of two layers, the state of six layers does not
    know the positions."""
    small = harness.load_cell(CELL, rehearse=True)["config"]
    model = harness.builder_for(small).build(small, 1, training=False)
    assert sum(p.size for p in model.parameters()) == _parameters(small)[0]
    cfg = harness.load_cell(CELL)["config"]
    total, gqa, kda, ff = _parameters(cfg)
    assert total == 2_640_502_400 and "2,640,502,400" in cfg["notes"]
    assert (round(kda / 1e5), round(gqa / 1e5), round(ff / 1e5)) \
        == (1377, 1091, 1743)
    from paddle_tpu.models.generation import CacheDescription, LaneState
    state = LaneState(((64, 128, 128), (3, 3 * 8192)))
    cache = CacheDescription([(8, 128, 128, None) if i in cfg["gqa_layers"]
                              else state
                              for i in range(cfg["num_hidden_layers"])])
    assert (cache.n_full, cache.n_state, cache.n_window) == (2, 6, 0)
    shapes = cache.pool_shapes(4097, 16, 8)
    assert shapes[0] == ((8, 4097, 16, 128),) * 2
    assert shapes[1] == ((8, 64, 128, 128), (8, 3, 24576))
    size = lambda pair: sum(4 * _prod(shape) for shape in pair)
    assert 1.07e9 < size(shapes[0]) + size(shapes[4]) < 1.08e9
    assert 0.21e9 < 6 * size(shapes[1]) < 0.22e9
    assert cache.pool_shapes(99, 16, 8)[1] == shapes[1]
    # a slot column behind the page ids, and off again inside a step
    import numpy as np
    t = cache.tables(np.zeros((2, 5), "int32"), [7, 3], 0)
    assert t.shape == (2, 6) and t[:, -1].tolist() == [7, 3]


def _prod(shape):
    n = 1
    for v in shape:
        n *= v
    return n


_CFG = {"linear_attn_config": {"head_dim": 128, "num_heads": 64},
        "num_hidden_layers": 8, "gqa_layers": [0, 4],
        "n_routed_experts": 10, "hidden_size": 4096,
        "moe_intermediate_size": 1280, "serve": {"dtype": "float32"}}


def _step(ts, q_width=1, prefill=0, **over):
    s = {"ts": ts, "q_width": q_width, "prefill_seqs": prefill,
         "step_s": 0.02, "state_lanes": 8, "state_resets": 0,
         "scan_rows": 0, "experts_hit": 15, "expert_rows": 16,
         "expert_rows_max": 3}
    s.update(over)
    return s


def test_bytes_and_operations_are_counted_from_the_configuration():
    # one lane, six layers: 64 heads x 128 x 128 float32 read and written
    nbytes, ops = la.step_bytes_ops(_CFG, 1)
    assert nbytes == 6 * 2 * 64 * 128 * 128 * 4 == 50_331_648
    assert ops == 6 * 7 * 64 * 128 * 128
    assert la.step_bytes_ops(_CFG, 8) == (8 * nbytes, 8 * ops)
    # a chunk of 1,024 rows of one sequence: q, k, g, v in and o out a
    # row, the state in and out once, the recurrence's operations a row
    nbytes, ops = la.scan_bytes_ops(_CFG, 1024, 1)
    assert nbytes == 6 * 64 * 4 * (1024 * 5 * 128 + 2 * 128 * 128)
    assert ops == 1024 * 6 * 7 * 64 * 128 * 128
    assert la.scan_bytes_ops(_CFG, 0, 0) == (0.0, 0.0)


_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 10 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 40000000 duration_ps: 60000000 }
    events { metadata_id: 12 offset_ps: 100000000 duration_ps: 1000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 24000000 duration_ps: 6000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 30000000 }
    events { metadata_id: 5 offset_ps: 45000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 70000000 duration_ps: 2000000 }
    events { metadata_id: 7 offset_ps: 72000000 duration_ps: 8000000 } }
  stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,4096] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/linear_attention/mul" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8,64,128,128] fusion()"
    stats { metadata_id: 1 str_value: "loop fusion" }
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/linear_attention/linear_attn_step/select_n" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8,4096] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/shared_expert/dot_general" } } }
  event_metadata { key: 4 value { id: 4 name: "%while.4 = (s32[], f32[8,64,128,128]) while()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/linear_attention/linear_attn_scan/while" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = f32[64,64,128] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/linear_attention/linear_attn_scan/while/body/dot_general" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[8,64,128,128] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/linear_attention/linear_attn_step/select_n" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[1032,4096] fusion()" } }
  event_metadata { key: 10 value { id: 10 name: "jit_serve_step_q1(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_serve_step_q1024(456)" } }
  event_metadata { key: 12 value { id: 12 name: "jit_convert_element_type(7)" } }
}
planes { name: "/host:CPU" }
"""


def test_scopes_are_read_from_the_events_metadata(tmp_path):
    """An operation's scope is the ``tf_op`` stat of its event's
    metadata; a ``while`` spans its body, so seconds are a union; the
    one-token update counts in the decode-only program, the scan in the
    wider ones; only the engine's programs count as runs."""
    from jax.profiler import ProfileData
    raw = la.read_scopes(ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    assert (raw["narrow_runs"], raw["wide_runs"]) == (2, 1)
    assert la._union_s(raw["time"]) == pytest.approx((20 + 30 + 2) * 1e-6)
    assert la._union_s(raw["step"]) == pytest.approx(12e-6)
    assert la._union_s(raw["scan"]) == pytest.approx(30e-6)   # not 40
    assert la._union_s([]) == 0.0

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    steps = [_step(10.0), _step(10.5, state_lanes=4),
             _step(11.0, q_width=1024, prefill=1, scan_rows=1000),
             _step(99.0, state_lanes=1)]             # outside the stretch
    obs = {"config": _CFG, "batch_steps": steps, "traced_wall": (9.5, 14.0),
           "device_kind": "TPU v5 lite", "xplane_path": str(path),
           "trace": {"busy_s": 104e-6, "window_s": 110e-6}}
    assert la.linear_attn_time_pct(obs) == pytest.approx(100 * 52 / 104)
    # narrow steps: (8 + 4) / 2 lanes' states a step over 819 GB/s,
    # against 12 us under linear_attn_step in two runs
    step_s = 6 * 50_331_648 / 819e9
    assert la.linear_attn_step_roofline_pct(obs) == pytest.approx(
        100 * step_s / 6e-6)
    scan_s = la.scan_bytes_ops(_CFG, 1000, 1)[0] / 819e9
    assert la.linear_attn_scan_roofline_pct(obs) == pytest.approx(
        100 * scan_s / 30e-6)
    # on the chip a trace without such operations gives nothing
    bare = dict(obs, linear_attn={"time_s": 0.0, "scan_s": 0.0,
                                  "step_s": 0.0, "narrow_runs": 2,
                                  "wide_runs": 1})
    assert all(f(bare) is None for f in (
        la.linear_attn_time_pct, la.linear_attn_step_roofline_pct,
        la.linear_attn_scan_roofline_pct))
    # records of a program without the counters read as nothing
    old = dict(obs, batch_steps=[{"ts": 10.0, "q_width": 1, "step_s": 0.02,
                                  "prefill_seqs": 0}])
    assert la.linear_attn_step_roofline_pct(old) is None
    # a rehearsal (no TPU): the arithmetic runs on step_s, and the time
    # share is the linear layers' share of the layers
    cpu = dict(obs, device_kind="cpu")
    assert la.linear_attn_step_roofline_pct(cpu) == pytest.approx(
        100 * step_s / 0.02)
    assert la.linear_attn_time_pct(cpu) == pytest.approx(75.0)


def test_expert_metrics_count_every_layer_as_an_expert_layer():
    steps = [_step(10.0), _step(10.5, experts_hit=5, expert_rows=8,
                                expert_rows_max=1),
             _step(11.0, q_width=1024, prefill=1, experts_hit=80,
                   expert_rows=2000, expert_rows_max=40)]
    obs = {"config": _CFG, "batch_steps": steps, "traced_wall": (9.5, 14.0),
           "device_kind": "TPU v5 lite",
           "kernel_s": {
               # 3 narrow steps seen by the two GQA layers' kernel
               "full": {"narrow_s": 6e-3, "narrow_n": 6, "all_s": 1.0,
                        "all_n": 8},
               "expert": {"narrow_s": 3 * 2e-3, "narrow_n": 30,
                          "all_s": 1.0, "all_n": 90}}}
    held = 10 * 8
    assert eel.experts_hit_pct(obs) == pytest.approx(
        100 * (15 + 5) / (2 * held))
    assert eel.expert_rows_max_over_mean(obs) == pytest.approx(
        (3 + 1) / ((16 + 8) / held))
    one = 3 * 4096 * 1280 * 4
    assert eel.expert_matmul_roofline_pct(obs) == pytest.approx(
        100 * (10 * one / 819e9) / 2e-3)
    del obs["kernel_s"]["full"]
    assert eel.expert_matmul_roofline_pct(obs) is None
    obs["device_kind"] = "cpu"
    assert eel.expert_matmul_roofline_pct(obs) == pytest.approx(
        100 * (10 * one / 819e9) / 0.02)
    assert eel.experts_hit_pct({"config": _CFG}) is None
