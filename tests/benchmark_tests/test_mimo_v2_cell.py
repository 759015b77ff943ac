"""The benchmark's side of the third configuration
(``mimo-v2.5-7l-ep32``): its file against the published values, the
runner ``serve_described`` beside the runner ``serve`` on Mistral's
files, and the new readers' arithmetic on hand-made samples.
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

from benchmark import harness                               # noqa: E402
from benchmark.layer_metrics import experts_window as ew    # noqa: E402
from benchmark.runners import serve_described               # noqa: E402

CELL = "mimo-v2.5-7l-ep32.longgen"

# https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json, the
# language model's keys (lists of 48 written as their rule)
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": [int(i % 6 != 5 and i != 0)
                             for i in range(48)],
    "intermediate_size": 16384, "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576, "model_type": "mimo_v2",
    "moe_intermediate_size": 2048,
    "moe_layer_freq": [int(i > 0) for i in range(48)], "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": None,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 152576}


def test_the_file_holds_the_published_values_but_for_what_it_reduces():
    cfg = harness.load_cell(CELL)["config"]
    reduced = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
               "n_routed_experts", "vocab_size", "max_position_embeddings",
               "torch_dtype"]
    assert cfg["reduced"] == reduced
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # the cut: the first seven layers, an expert-parallel share of 1/32,
    # an eighth of the vocabulary
    assert cfg["hybrid_layer_pattern"] == PUBLISHED["hybrid_layer_pattern"][:7]
    assert cfg["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == PUBLISHED["moe_layer_freq"][:7]
    assert cfg["num_hidden_layers"] == 7
    assert cfg["n_routed_experts"] * 32 == PUBLISHED["n_routed_experts"] \
        == cfg["n_router_outputs"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["first_held_expert"] == 0
    s = cfg["serve"]
    assert s["num_pages"] == s["max_batch"] \
        * cfg["max_position_embeddings"] // s["page_size"] + 1
    assert s["prefix_caching"] is False and s["max_prefill_chunk"] == 1024
    # the longest request of the mix fits a lane
    mix = harness.load_cell(CELL)["traffic"]
    assert mix["prompt"]["max"] + mix["output"]["max"] \
        <= cfg["max_position_embeddings"]
    # ISSUE 27's sizes, unlike from request to request: prompts cross the
    # chunk (most more than once) and leave ragged remainders
    assert mix["prompt"] == {"kind": "lognormal", "median": 2048,
                             "sigma": 0.5, "min": 1024, "max": 4096}
    assert mix["output"] == {"kind": "lognormal", "median": 256,
                             "sigma": 0.5, "min": 128, "max": 512}
    assert (mix["clients"], mix["pool"], mix["loop"]) == (16, 32, "closed")
    assert mix["prompt"]["min"] >= s["max_prefill_chunk"]


def test_the_parameters_and_the_pools_are_what_the_notes_say():
    """2.22 B parameters from the shapes; pools of a ring for the window
    layers and of every position for the full ones."""
    cfg = harness.load_cell(CELL)["config"]
    h, nh, dk, dv = 4096, 64, 192, 128
    attn = lambda kv: h * (nh * dk + kv * (dk + dv)) + nh * dv * h
    dense = 3 * h * cfg["intermediate_size"]
    experts = cfg["n_routed_experts"] * 3 * h * cfg["moe_intermediate_size"] \
        + h * cfg["n_router_outputs"]
    total = 2 * cfg["vocab_size"] * h
    for window, moe in zip(cfg["hybrid_layer_pattern"],
                           cfg["moe_layer_freq"]):
        total += attn(8 if window else 4) + (experts if moe else dense)
    assert round(total / 1e6) == 2222
    from paddle_tpu.models.generation import CacheDescription, _pool_width
    cache = CacheDescription([(8 if w else 4, _pool_width(dk), dv,
                               128 if w else None)
                              for w in cfg["hybrid_layer_pattern"]])
    ring = cache.ring_pages(16, 1024)
    assert ring == 73
    shapes = cache.pool_shapes(4097, 16, 8, ring)
    assert shapes[0] == ((4, 4097, 16, 256), (4, 4097, 16, 128))
    assert shapes[1] == ((8, 585, 16, 256), (8, 585, 16, 128))
    nbytes = sum(4 * a * b * c * d for pair in shapes for a, b, c, d in pair)
    assert 1.35e9 < nbytes < 1.40e9


def test_chunk_buckets_are_the_widths_a_chunked_mix_reaches():
    cb = serve_described.chunk_buckets
    assert cb([100, 600, 1024], 0) == [128, 1024]
    assert cb([1024, 1500, 2048, 4096], 1024) == [512, 1024]
    assert cb([1025, 3000], 1024) == [1, 1024]            # 3000 = 2c + 952
    assert cb([24, 40, 64], 16) == [8, 16]
    from benchmark import generator
    mix = harness.load_cell(CELL)["traffic"]
    lens = generator.Requests(mix, 19072, 7, mix["pool"]).prompt_len
    # every seed draws the same set of lengths in another order, so every
    # seed warms the same widths: the chunk and the remainders' buckets
    widths = cb(lens, 1024)
    assert widths[-1] == 1024 and len(widths) >= 4
    assert cb(generator.Requests(mix, 19072, 3_000_000_019,
                                 mix["pool"]).prompt_len, 1024) == widths


_BOTH_RUNNERS = """
import os, sys, types
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["FLAGS_pallas_interpret"] = "1"
sys.path.insert(0, %(root)r)
from benchmark import harness
import jax
import paddle_tpu
from benchmark.runners import serve, serve_described
cell = harness.load_cell("mistral-7b-8l.batch", True)
for runner in (serve, serve_described):
    args = types.SimpleNamespace(
        seed=3000000019, seconds=2.0, trace=1, rehearse=True, sweep=None,
        out=os.path.join(%(out)r, runner.__name__))
    os.makedirs(args.out)
    print("LINE " + runner.run(cell, args, harness.SetupClock()))
"""


def test_serve_described_runs_mistrals_files_and_prints_serves_keys(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    p = subprocess.run(
        [sys.executable, "-c",
         _BOTH_RUNNERS % {"root": ROOT, "out": str(tmp_path)}],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    old, new = (json.loads(line.removeprefix("LINE "))
                for line in p.stdout.splitlines()
                if line.startswith("LINE "))
    assert old["correct"] is True and new["correct"] is True, p.stdout[-3000:]
    assert set(old) == set(new)
    assert set(old["metrics"]) == set(new["metrics"])
    assert set(old["device"]) == set(new["device"])
    assert set(old["breakdown"]) == set(new["breakdown"])
    assert new["failed"] == 0 and new["attempted"] > 0
    # the logits check of the new runner ran on Mistral's stack, through
    # pools it asked the program for
    assert "through the ragged step against the float32 reference" in p.stdout
    assert p.stdout.count("nothing compiled inside the window") == 2


# --- the readers on hand-made samples --------------------------------------

_CFG = {"hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
        "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "n_routed_experts": 8,
        "num_attention_heads": 64, "num_key_value_heads": 4,
        "swa_num_key_value_heads": 8, "head_dim": 192, "v_head_dim": 128,
        "hidden_size": 4096, "moe_intermediate_size": 2048,
        "serve": {"page_size": 16, "dtype": "float32"}}


def _step(ts, q_width=1, prefill=0, **kw):
    base = {"ts": ts, "q_width": q_width, "prefill_seqs": prefill,
            "step_s": 0.02, "expert_rows": 12, "expert_rows_max": 2,
            "experts_hit": 10, "window_pages_read": 8 * 9 * 5,
            "full_pages_read": 8 * 150 * 2}
    base.update(kw)
    return base


def test_counter_metrics_are_ratios_of_sums_over_decode_steps():
    obs = {"config": _CFG, "batch_steps": [
        _step(1.0), _step(2.0, expert_rows=0, expert_rows_max=0,
                          experts_hit=0),
        _step(3.0, q_width=1024, prefill=1, expert_rows=900,
              expert_rows_max=40, experts_hit=48)]}
    # 48 held experts over six layers; 12 rows in two decode steps
    assert ew.experts_hit_pct(obs) == pytest.approx(100 * 10 / (2 * 48))
    assert ew.expert_rows_max_over_mean(obs) == pytest.approx(2 / (12 / 48))
    # 9 pages of 150: five window layers against the two full ones
    assert ew.window_pages_read_pct(obs) == pytest.approx(100 * 9 / 150)
    # a program without the fields, or no decode step: nothing to read
    old = {"config": _CFG, "batch_steps": [
        {"ts": 1.0, "q_width": 1, "prefill_seqs": 0, "step_s": 0.02}]}
    for reader in (ew.experts_hit_pct, ew.expert_rows_max_over_mean,
                   ew.window_pages_read_pct,
                   ew.ragged_attn_window_roofline_pct,
                   ew.expert_matmul_roofline_pct):
        assert reader(old) is None and reader({}) is None


def test_bytes_and_operations_are_counted_from_the_configuration():
    # a page of a window layer: 16 slots x 8 kv heads x (256 + 128) x 4 B
    nbytes, ops = ew.attention_bytes_ops(_CFG, 1, window=True)
    assert nbytes == 16 * 8 * 384 * 4 == 196608
    assert ops == 16 * 64 * 2 * (192 + 128)
    assert ew.attention_bytes_ops(_CFG, 10, window=False)[0] \
        == 10 * 16 * 4 * 384 * 4
    # an expert: three matrices of 4096 x 2048 in float32, 100.7 MB
    nbytes, ops = ew.expert_bytes_ops(_CFG, 2, 5)
    assert nbytes == 2 * 3 * 4096 * 2048 * 4
    assert ops == 5 * 2 * 3 * 4096 * 2048


def test_roofline_share_is_roofline_seconds_over_device_seconds_a_step():
    steps = [_step(10.0 + i) for i in range(4)] \
        + [_step(11.5, q_width=1024, prefill=1)] \
        + [_step(99.0, window_pages_read=10 ** 6)]       # outside the stretch
    obs = {"config": _CFG, "batch_steps": steps, "traced_wall": (9.5, 14.0),
           "device_kind": "TPU v5 lite",
           "kernel_s": {
               # 3 narrow steps seen: 15 window and 6 full operations
               "window": {"narrow_s": 15 * 50e-6, "narrow_n": 15,
                          "all_s": 1.0, "all_n": 20},
               "full": {"narrow_s": 6 * 1e-3, "narrow_n": 6,
                        "all_s": 1.0, "all_n": 8},
               "expert": {"narrow_s": 3 * 2e-3, "narrow_n": 90,
                          "all_s": 1.0, "all_n": 200}}}
    window_s = 360 * 196608 / 819e9                  # bytes bound
    assert ew.ragged_attn_window_roofline_pct(obs) == pytest.approx(
        100 * window_s / (5 * 50e-6))
    full_s = 2400 * 98304 / 819e9
    assert ew.ragged_attn_full_roofline_pct(obs) == pytest.approx(
        100 * full_s / (2 * 1e-3))
    expert_s = 10 * 3 * 4096 * 2048 * 4 / 819e9
    assert ew.expert_matmul_roofline_pct(obs) == pytest.approx(
        100 * expert_s / 2e-3)
    assert all(0 < f(obs) < 100 for f in (
        ew.ragged_attn_window_roofline_pct,
        ew.ragged_attn_full_roofline_pct, ew.expert_matmul_roofline_pct))
    # on the chip a trace without the kernel's operations gives nothing:
    # a device share is never computed from the host's step_s there
    del obs["kernel_s"]["window"]
    assert ew.ragged_attn_window_roofline_pct(obs) is None
    assert ew.ragged_attn_full_roofline_pct(obs) is not None
    del obs["kernel_s"]
    assert all(f(obs) is None for f in (
        ew.ragged_attn_window_roofline_pct,
        ew.ragged_attn_full_roofline_pct, ew.expert_matmul_roofline_pct))
    # a rehearsal (no TPU): the arithmetic runs on step_s
    obs["device_kind"] = "cpu"
    assert ew.ragged_attn_window_roofline_pct(obs) == pytest.approx(
        100 * window_s / 0.02)


_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 40000000 }
    events { metadata_id: 5 offset_ps: 47000000 duration_ps: 9000000 }
    events { metadata_id: 6 offset_ps: 56000000 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 61000000 duration_ps: 7000000 } }
  event_metadata { key: 1 value { id: 1 name:
    "%ragged_paged_attn_window.3 = f32[8,64,8,128]{3,2,1,0} custom-call(s32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name:
    "%ragged_paged_attn.7 = f32[8,64,1024,128]{3,2,1,0} custom-call(s32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name:
    "%conditional.4 = (f32[8,4096]{1,0}) conditional(pred[] %gt, (f32[8,4096]) %t)" } }
  event_metadata { key: 4 value { id: 4 name:
    "%fusion.9 = bf16[8,64,128]{2,1,0} fusion(f32[8,64,8,128]{3,2,1,0} %ragged_paged_attn_window.3)" } }
  event_metadata { key: 5 value { id: 5 name:
    "%conditional.5 = (f32[8192,4096]{1,0}) conditional(pred[] %gt, (f32[8192,4096]) %t)" } }
  event_metadata { key: 6 value { id: 6 name:
    "%ragged_paged_attn = f32[8,64,8,128]{3,2,1,0} custom-call(s32[8]{0} %p)" } }
  event_metadata { key: 7 value { id: 7 name:
    "%conditional.8 = (s32[8]{0}) conditional(pred[] %gt, (s32[8]) %t)" } }
}
"""


def test_kernel_seconds_are_summed_by_an_operations_own_name(tmp_path):
    """A consumer that names a kernel among its operands is not the
    kernel; a conditional is narrow when the attention kernel before it
    was, and is the expert loop's only where its output is the layer's
    [rows, hidden]."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_XSPACE))
    obs = {"config": _CFG}
    ew.observe_kernels(str(path), obs)
    k = obs["kernel_s"]
    assert k["window"] == {"narrow_s": pytest.approx(3e-6), "narrow_n": 1,
                           "all_s": pytest.approx(3e-6), "all_n": 1}
    assert k["full"] == {"narrow_s": pytest.approx(5e-6), "narrow_n": 1,
                         "all_s": pytest.approx(45e-6), "all_n": 2}
    assert k["expert"] == {"narrow_s": pytest.approx(2e-6), "narrow_n": 1,
                           "all_s": pytest.approx(11e-6), "all_n": 2}
    ew.observe_kernels(None, obs)                    # no trace: untouched
