"""The benchmark's side of the fifth configuration (``glm-5-5l-ep32``):
its file against the catalog's numbers, its sizes against the file's
notes, the new readers' arithmetic on hand-made samples and on a small
trace built here, and the runner's check lengths.  The rehearsal of the
cell, untraced and traced with every declared metric a number, is
``test_benchmark_rehearse.py``'s, which finds the cell's file by itself.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import generator, harness                        # noqa: E402
from benchmark.layer_metrics import latent_select as lsel       # noqa: E402
from benchmark.runners import serve_described, serve_selected   # noqa: E402

CELL = "glm-5-5l-ep32.longctx"

# https://huggingface.co/zai-org/GLM-5/blob/main/config.json, the
# language model's keys as the catalog beside the model-configs guide
# holds them (row "GLM-5")
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 256, "vocab_size": 154880}


def test_the_file_holds_the_published_values_but_for_what_it_reduces():
    cell = harness.load_cell(CELL)
    cfg, mix = cell["config"], cell["traffic"]
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "max_position_embeddings",
               "torch_dtype", "num_nextn_predict_layers"]
    assert cfg["reduced"] == reduced
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    # no reduced key is a width
    assert not [k for k in reduced if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the cut: a leading dense layer and four expert layers, a share of
    # 1/32 of the experts, an eighth of the vocabulary, no MTP layer;
    # the file states the published numbers
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 0)
    assert cfg["n_routed_experts"] * 32 == PUBLISHED["n_routed_experts"] \
        == cfg["n_router_outputs"]
    assert cfg["n_routed_experts"] >= 8 and cfg["first_held_expert"] == 0
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    said = " ".join(cfg["assumed"]) + cfg["notes"]
    for number in ("78 layers", "256 routed experts", "154880", "32 chips",
                   "202752", "bfloat16", "first_k_dense_replace 3"):
        assert number in said, number
    assert [a[:3] for a in cfg["assumed"][:5]] \
        == ["(a)", "(b)", "(c)", "(d)", "(e)"]
    s = cfg["serve"]
    assert s["num_pages"] == s["max_batch"] \
        * cfg["max_position_embeddings"] // s["page_size"] + 1
    assert s["prefix_caching"] is False and s["max_prefill_chunk"] == 1024
    # ISSUE 33's mix: a few thousand tokens in, a short answer out, and
    # every prompt longer than the selection keeps
    assert mix["prompt"] == {"kind": "lognormal", "median": 4096,
                             "sigma": 0.4, "min": 2304, "max": 7680}
    assert mix["output"] == {"kind": "lognormal", "median": 128,
                             "sigma": 0.5, "min": 64, "max": 256}
    assert (mix["clients"], mix["pool"], mix["loop"]) == (16, 32, "closed")
    assert mix["runner"] == "serve_selected"
    assert mix["prompt"]["min"] > cfg["index_topk"]
    # the longest request fits a lane, and eight of them the pool
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert longest <= cfg["max_position_embeddings"]
    assert s["max_batch"] * -(-longest // s["page_size"]) < s["num_pages"]
    lens = generator.lengths(mix["prompt"], mix["pool"])
    assert min(lens) > cfg["index_topk"]
    buckets = serve_described.chunk_buckets(lens, 1024)
    assert buckets[-1] == 1024 and all(q & (q - 1) == 0 for q in buckets)
    assert cell["end_to_end"] == ["serve_tokens_per_s", "setup_s"]
    # the precision the file states is the description's
    described = harness.builder_for(cfg)._model_config(cfg).description()
    assert described.precision == "high" and "precision high" in cfg["precision"]
    # the check feeds a prompt of more than twice what the selection
    # keeps, which serve_described's 1,500 and 300 tokens never reach
    assert serve_selected.CHECK_PROMPTS == (4500, 300)
    assert max(serve_selected.CHECK_PROMPTS) > 2 * cfg["index_topk"] \
        > 2 * max(serve_described._CHECK_PROMPTS)


def _parameters(cfg):
    """Parameters of the chip's share, from the file's sizes."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    qr, nope, dv = cfg["q_lora_rank"], cfg["qk_nope_head_dim"], \
        cfg["v_head_dim"]
    ih, d = cfg["index_n_heads"], cfg["index_head_dim"]
    mixer = h * qr + qr + qr * nh * (nope + rope) + h * (rank + rope) \
        + rank + nh * rank * (nope + dv) + nh * dv * h
    index = qr * ih * d + h * d + 2 * d + h * ih
    one = 3 * h * cfg["moe_intermediate_size"]
    experts = h * cfg["n_router_outputs"] + cfg["n_router_outputs"] \
        + (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * one
    dense = 3 * h * cfg["intermediate_size"]
    total = 2 * cfg["vocab_size"] * h + h
    for i in range(cfg["num_hidden_layers"]):
        total += mixer + index + 2 * h + (
            dense if i < cfg["first_k_dense_replace"] else experts)
    return total, mixer, index, experts, dense


def test_the_parameters_and_the_pools_are_what_the_notes_say():
    """The count by hand equals the program's at the rehearsal's sizes,
    and gives the notes' 2,701.7 M at the published ones; a token leaves
    (640 + 128) x 4 B a layer in two pools with no axis of heads."""
    small = harness.load_cell(CELL, rehearse=True)["config"]
    model = harness.builder_for(small).build(small, 1, training=False)
    assert sum(p.size for p in model.parameters()) == _parameters(small)[0]
    cfg = harness.load_cell(CELL)["config"]
    total, mixer, index, experts, dense = _parameters(cfg)
    assert round(total / 1e5) == 27017 and "2,701.7 M" in cfg["notes"]
    assert (round(mixer / 1e5), round(index / 1e5)) == (1650, 94)
    assert round((mixer + index + experts) / 1e5) == 5157
    assert round((mixer + index + dense) / 1e5) == 4009
    assert 10.80e9 < 4 * total < 10.82e9
    described = harness.builder_for(cfg)._model_config(cfg).description()
    from paddle_tpu.models.generation import (CacheDescription, LatentPages,
                                              _latent_pages)
    pages = _latent_pages(described.layers[0].latent_attention)
    assert pages == LatentPages((640, 128))
    cache = CacheDescription([pages] * cfg["num_hidden_layers"])
    assert (cache.n_latent, cache.n_full, cache.n_state, cache.n_window) \
        == (5, 0, 0, 0)
    shapes = cache.pool_shapes(4097, 16, 8)
    assert shapes[0] == ((1, 4097, 16, 640), (1, 4097, 16, 128))
    nbytes = sum(4 * _prod(shape) for layer in shapes for shape in layer)
    assert nbytes == 4097 * 16 * 5 * 3072 and 1.00e9 < nbytes < 1.01e9
    assert "3,072 B" in cfg["notes"] and "15,360 B" in cfg["notes"]
    # tables are the full layers' own: no ring, no slot column
    import numpy as np
    t = cache.tables(np.zeros((2, 5), "int32"), [7, 3], 0)
    assert t.shape == (2, 5)


def _prod(shape):
    n = 1
    for v in shape:
        n *= v
    return n


_CFG = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "hidden_size": 6144,
        "moe_intermediate_size": 2048, "num_attention_heads": 64,
        "kv_lora_rank": 512, "qk_rope_head_dim": 64, "index_n_heads": 32,
        "index_head_dim": 128, "index_topk": 2048,
        "serve": {"dtype": "float32"}}


def _step(ts, q_width=1, prefill=0, **over):
    s = {"ts": ts, "q_width": q_width, "prefill_seqs": prefill,
         "step_s": 0.02, "experts_hit": 12, "expert_rows": 16,
         "expert_rows_max": 3, "select_rows": 8, "keys_visible": 40000,
         "keys_selected": 16384}
    s.update(over)
    return s


def test_bytes_and_operations_are_counted_from_the_configuration():
    # eight decoding lanes that see 40,000 keys in all and keep 2,048
    # each, one layer: 32 heads x 128 products a key; the lanes' index
    # keys read once
    nbytes, ops = lsel.index_score_bytes_ops(_CFG, 40000, 16384, 8, True)
    assert ops == 2 * 40000 * 32 * 128 == 327_680_000
    assert nbytes == 40000 * 128 * 4
    assert lsel.index_score_bytes_ops(_CFG, 40000, 16384, 8, False) \
        == (0.0, ops)
    # a chunk of 1,024 rows at positions 1,500..2,523: the 476 rows past
    # 2,047 score the 2,049..2,524 keys they see, the others none
    seen = sum(range(1501, 2525))
    kept = sum(min(v, 2048) for v in range(1501, 2525))
    _, ops = lsel.index_score_bytes_ops(_CFG, seen, kept, 476, False)
    assert ops == 2 * sum(range(2049, 2525)) * 32 * 128
    assert lsel.index_score_bytes_ops(_CFG, 3000, 3000, 0, True) == (0, 0)
    # ... and keep 2,048 each: a kept key is a 640-wide row of the pool,
    # 64 heads' logit over 576 and weighted sum over 512
    nbytes, ops = lsel.attend_bytes_ops(_CFG, 8 * 2048, True)
    assert nbytes == 8 * 2048 * 640 * 4 == 41_943_040
    assert ops == 2 * 64 * 8 * 2048 * (576 + 512)
    assert lsel.attend_bytes_ops(_CFG, 8 * 2048, False) == (0.0, ops)
    assert nbytes / 819e9 > ops / 197e12       # a decode step is bytes
    assert lsel.expert_layers(_CFG) == 4


_XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 10 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 11 offset_ps: 40000000 duration_ps: 60000000 }
    events { metadata_id: 12 offset_ps: 100000000 duration_ps: 1000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 5000000 }
    events { metadata_id: 8 offset_ps: 9000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 23000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 40000000 duration_ps: 30000000 }
    events { metadata_id: 5 offset_ps: 41000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 46000000 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 72000000 duration_ps: 8000000 } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8,32,8192] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/latent_attention/index_select/index_score/dot_general" } } }
  event_metadata { key: 2 value { id: 2 name: "%sort.2 = f32[8,8192] sort()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/latent_attention/index_select/index_topk/cond" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[8,2048,640] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/latent_attention/sparse_attention/gather" } } }
  event_metadata { key: 4 value { id: 4 name: "%while.4 = (s32[], f32[1160,64,640]) while()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/latent_attention/while" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = u32[128,1024] fusion()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/latent_attention/while/body/index_select/index_score/while/body/dot_general" } } }
  event_metadata { key: 6 value { id: 6 name: "%while.6 = (s32[], f32[128,64,640]) while()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1024)/latent_attention/while/body/sparse_attention/while" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[1032,6144] fusion()" } }
  event_metadata { key: 8 value { id: 8 name: "%conditional.8 = f32[8,6144] conditional()"
    stats { metadata_id: 2 str_value: "jit(serve_step_q1)/expert_matmul/cond" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_serve_step_q1(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_serve_step_q1024(456)" } }
  event_metadata { key: 12 value { id: 12 name: "jit_convert_element_type(7)" } }
}
planes { name: "/host:CPU" }
"""


def test_scopes_are_read_from_the_events_metadata(tmp_path):
    """An operation's scope is the ``tf_op`` stat of its event's
    metadata, an operation named as a scope (``.../expert_matmul/cond``)
    lies under it, a ``while`` spans its body; the decode-only program's
    operations are told from the wider ones'."""
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(_XSPACE)
    raw = lsel.read_scopes(blob)
    assert (raw["narrow_runs"], raw["wide_runs"]) == (2, 1)
    us = lambda spans: round(lsel.la._union_s(spans) * 1e6, 6)
    assert us(raw["index_score"]["narrow"]) == 4
    assert us(raw["index_topk"]["narrow"]) == 1
    assert us(raw["index_select"]["narrow"]) == 5
    assert us(raw["index_select"]["wide"]) == 4
    assert us(raw["sparse_attention"]["narrow"]) == 10
    assert us(raw["sparse_attention"]["wide"]) == 20
    assert us(raw["latent_attention"]["wide"]) == 30          # not 54
    assert us(raw["expert_matmul"]["narrow"]) == 4

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(blob)
    steps = [_step(10.0), _step(10.5, keys_visible=20000,
                                keys_selected=8192, experts_hit=4,
                                expert_rows=8, expert_rows_max=1),
             _step(11.0, q_width=1024, prefill=1, keys_visible=3_000_000,
                   keys_selected=2_000_000, select_rows=900),
             _step(99.0)]                            # outside the stretch
    obs = {"config": _CFG, "batch_steps": steps, "traced_wall": (9.5, 14.0),
           "device_kind": "TPU v5 lite", "xplane_path": str(path),
           "trace": {"busy_s": 100e-6, "window_s": 110e-6}}
    assert lsel.keys_selected_pct(obs) == pytest.approx(
        100 * (16384 + 8192 + 2_000_000 + 16384)
        / (40000 + 20000 + 3_000_000 + 40000))
    assert lsel.index_select_time_pct(obs) == pytest.approx(100 * 9 / 100)
    assert lsel.sparse_attn_time_pct(obs) == pytest.approx(100 * 30 / 100)
    # decode-only steps: (16384 + 8192) / 2 kept rows of 2,560 B a layer,
    # five layers, against 10 us under sparse_attention in two runs
    want = 5 * 12288 * 2560 / 819e9
    assert lsel.sparse_attn_decode_roofline_pct(obs) == pytest.approx(
        100 * want / 5e-6)
    # the step with a chunk: operations over the kept keys alone
    want = 5 * 2 * 64 * 2_000_000 * 1088 / 197e12
    assert lsel.sparse_attn_prefill_roofline_pct(obs) == pytest.approx(
        100 * want / 20e-6)
    # index scores over all three steps, 8 us in three runs: the decode
    # steps' index keys read (bytes) or the products, whichever is more
    # (8 lanes chose in each decode step, 900 rows in the wide one)
    narrow = lambda seen: 5 * max(seen * 512 / 819e9,
                                  2 * seen * 4096 / 197e12)
    wide = 5 * 2 * (1_000_000 + 900 * 2048) * 4096 / 197e12
    assert lsel.index_score_roofline_pct(obs) == pytest.approx(
        100 * (narrow(40000) + narrow(20000 - 8192 + 16384) + wide) / 3
        / (8e-6 / 3))
    # experts: (12 + 4) / 2 hit a decode-only step against 4 us in two
    one = 3 * 6144 * 2048 * 4
    assert lsel.expert_matmul_roofline_pct(obs) == pytest.approx(
        100 * (8 * one / 819e9) / 2e-6)
    held = 8 * 4
    assert lsel.experts_hit_pct(obs) == pytest.approx(
        100 * (12 + 4 + 12) / (3 * held))
    assert lsel.expert_rows_max_over_mean(obs) == pytest.approx(
        (3 + 1 + 3) / ((16 + 8 + 16) / held))
    # on the chip a trace without such operations gives nothing
    empty = {s: {"narrow_s": 0.0, "wide_s": 0.0, "all_s": 0.0}
             for s in lsel.SCOPES}
    bare = dict(obs, latent_select=dict(empty, narrow_runs=2, wide_runs=1))
    assert all(f(bare) is None for f in (
        lsel.index_select_time_pct, lsel.sparse_attn_time_pct,
        lsel.index_score_roofline_pct, lsel.sparse_attn_decode_roofline_pct,
        lsel.sparse_attn_prefill_roofline_pct,
        lsel.expert_matmul_roofline_pct))
    # records of a program without the counters read as nothing, and so
    # does a run of a program that lacks what this PR adds
    old = dict(obs, batch_steps=[{"ts": 10.0, "q_width": 1, "step_s": 0.02,
                                  "prefill_seqs": 0}])
    assert all(f(old) is None for f in (
        lsel.keys_selected_pct, lsel.index_score_roofline_pct,
        lsel.sparse_attn_decode_roofline_pct, lsel.experts_hit_pct,
        lsel.expert_matmul_roofline_pct))
    # a rehearsal (no TPU): the arithmetic runs on step_s
    cpu = dict(obs, device_kind="cpu")
    assert lsel.sparse_attn_decode_roofline_pct(cpu) == pytest.approx(
        100 * (5 * 12288 * 2560 / 819e9) / 0.02)
    assert lsel.index_select_time_pct(cpu) > 0


def test_every_declared_longctx_metric_has_its_file_and_reader():
    manifest = harness.load_manifest()
    declared = [m for m in manifest["per_layer"]
                if m["name"].endswith(".longctx")]
    files = harness.layer_metrics_for("longctx")
    assert sorted(m["name"] for m in declared) == sorted(files)
    assert len(declared) == 18
    for m in declared:
        spec = files[m["name"]]
        assert callable(harness.resolve(spec["reader"]))
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert m["workloads"] == [CELL]
    assert {m["layer"] for m in declared} == {
        "Engine step", "Scheduler", "Device", "Expert layer",
        "Latent attention"}
    [e2e] = [m for m in manifest["end_to_end"]
             if m["name"] == "serve_tokens_per_s"]
    assert e2e["workloads"][-1] == CELL
    [entry] = [c for c in manifest["configs"] if c["name"] == "glm-5-5l-ep32"]
    assert entry["source"].startswith(
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json")
    assert entry["reduced"] == harness.load_cell(CELL)["config"]["reduced"]


# errors of the ten checked rows and whether the run is correct
_HONEST = [4e-4, 2e-4, 7e-4, 5e-5, 6e-5, 5e-5, 4e-4, 3e-4, 6e-5, 5e-5]


def _with(rows, **at):
    out = list(rows)
    for i, e in at.items():
        out[int(i[1:])] = e
    return out


_VERDICTS = {
    "honest": (_HONEST, True),
    "one_flipped_row": (_with(_HONEST, r3=2.6e-2), True),
    "two_flipped_rows_in_one_check": (_with(_HONEST, r2=2.8e-2, r4=4.4e-2),
                                      True),
    "three_flipped_rows": (_with(_HONEST, r1=3e-2, r2=2.8e-2, r4=4.4e-2),
                           True),
    "four_rows_over": (_with(_HONEST, r1=3e-2, r2=2.8e-2, r3=2e-2, r4=4.4e-2),
                       False),
    "bfloat16_moves_every_row": (
        [6.5e-2, 6.8e-2, 1e-1, 8.5e-2, 1.6e-1, 1.6e-2, 1.6e-2, 1.4e-2,
         1.5e-2, 1.7e-2], False),
    "no_selection_bias_moves_the_rows_that_picked_a_held_expert": (
        [1.1e-1, 2.9e-3, 1.3e-1, 3.1e-3, 8.7e-2] * 2, False),
    "a_lost_write": (_with(_HONEST, r4=0.7), False),
    "every_row_a_little_off": ([4e-3, 5e-3, 6e-3, 7e-3, 4e-3] * 2, False),
    "not_finite": (_with(_HONEST, r1=float("nan")), False),
}


@pytest.mark.parametrize("case", sorted(_VERDICTS))
def test_the_checked_rows_are_read_as_a_set(case, monkeypatch, capsys):
    """The comparison that decides ``correct`` (``serve_selected.
    rows_agree``, the limits ``benchmark/reference/glm5.py``'s): every
    checked row within ``LOGITS_TOL`` but at most ``FLIPPED_ROWS``, which
    a flipped selection may have moved and which are held to
    ``FLIP_TOL``, and the median within ``MEDIAN_TOL``.  A lower
    precision or a left-out mechanism moves every row, or the median,
    and fails; so does one row moved further than an expert's term
    can."""
    import types
    import numpy as np
    from benchmark.builders import glm5 as builder
    from benchmark.reference import glm5 as ref
    assert builder.tolerances() == {
        "logits": ref.LOGITS_TOL, "flipped_rows": ref.FLIPPED_ROWS,
        "logits_flipped_row": ref.FLIP_TOL, "logits_median": ref.MEDIAN_TOL}
    assert (ref.LOGITS_TOL, ref.FLIPPED_ROWS, ref.FLIP_TOL, ref.MEDIAN_TOL) \
        == (1e-2, 3, 2e-1, 3e-3)
    errors, correct = _VERDICTS[case]
    assert serve_selected.rows_agree(errors, builder.tolerances()) == correct
    # ... and through the check itself: two sequences, the prompt's last
    # row and four decode steps each
    want = np.zeros((7, 8), np.float32)
    want[:, 0] = 1.0                       # the largest logit of a row
    notes = {"experts": np.ones((1, 7), np.float32),
             "keys": np.full((1, 7), np.inf, np.float32)}
    fake = types.SimpleNamespace(
        weights=lambda model: {},
        reference_logits_and_notes=lambda w, ids, cfg: (want, notes),
        reference_report=builder.reference_report,
        tolerances=builder.tolerances)
    got = [want[2:].copy(), want[2:].copy()]
    got[0][:, 1], got[1][:, 1] = errors[:5], errors[5:]
    monkeypatch.setattr(
        serve_selected, "step_logits",
        lambda *a, **k: ([np.zeros((7,), "int64")] * 2, [3, 3], got))
    model = types.SimpleNamespace(
        config=types.SimpleNamespace(max_position_embeddings=64))
    failures = []
    serve_selected.check_logits(model, fake, {"serve": {}}, 0, failures,
                                prompts=(3, 3), decodes=4)
    assert (not failures) == correct, capsys.readouterr().out
