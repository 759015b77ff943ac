"""What ``test_manifest_appended_33.py``'s pinned case held, less its
pin.

``test_manifest_appended_33.py::
test_what_follows_them_is_this_cells_alone`` (PR 33) asserts that
EXACTLY eighteen entries follow PR 31's in ``per_layer``: true of the
manifest PR 33 left, false of any manifest a later PR appends to — and
the driver takes new entries at the end of the list only.  That file is
the benchmark's, so ``tests/conftest.py`` marks the case an expected
failure.  Here, with no count of what follows and no word on where the
list ends, so that the next PR that appends breaks nothing: PR 33's
eighteen ``.longctx`` entries follow PR 31's fifteen, in their order
and untouched; the cells and configurations are the accepted ones,
first and in order; and whatever follows the eighteen names accepted
cells only.

``test_glm5_cell.py::
test_every_declared_longctx_metric_has_its_file_and_reader`` (PR 33)
pins the same count from the other side — exactly eighteen declared
names end in ``.longctx``, of five layers — and is marked too; the last
case here holds what it held for every mix, with no count: a declared
metric has its file and a reader that resolves, and a file its entry.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

from test_manifest_appended import ACCEPTED  # noqa: E402
from test_manifest_appended_33 import LONGDOC  # noqa: E402

MANIFEST = harness.load_manifest()
_BEFORE = len(ACCEPTED) + len(LONGDOC)

# per_layer's entries of PR 33, in its order
LONGCTX = [
    "decode_step_ms", "prefill_step_ms", "tpot_p90_ms", "device_idle_pct",
    "host_gap_ms", "dispatch_ms", "sched_plan_ms", "queue_wait_p90_ms",
    "step_rows_empty_pct", "experts_hit_pct", "expert_rows_max_over_mean",
    "expert_matmul_roofline_pct", "keys_selected_pct",
    "index_select_time_pct", "sparse_attn_time_pct",
    "index_score_roofline_pct", "sparse_attn_decode_roofline_pct",
    "sparse_attn_prefill_roofline_pct"]
CELLS = ["gpt3-760m.pretrain", "mistral-7b-8l.batch",
         "mimo-v2.5-7l-ep32.longgen", "solar-open2-8l-ep32.longdoc",
         "glm-5-5l-ep32.longctx"]


def test_pr33s_entries_follow_pr31s_in_order_and_untouched():
    theirs = MANIFEST["per_layer"][_BEFORE:_BEFORE + len(LONGCTX)]
    assert [m["name"] for m in theirs] == [n + ".longctx" for n in LONGCTX]
    files = harness.layer_metrics_for("longctx")
    for m in theirs:
        assert m["workloads"] == ["glm-5-5l-ep32.longctx"]
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key], (m["name"], key)


def test_what_follows_them_names_accepted_cells_only():
    for m in MANIFEST["per_layer"][_BEFORE + len(LONGCTX):]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS), m
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # the cells and configurations: the accepted ones first, in order
    assert [w["name"] for w in MANIFEST["workloads"]][:len(CELLS)] == CELLS
    assert [c["name"] for c in MANIFEST["configs"]][3:5] == [
        "solar-open2-8l-ep32", "glm-5-5l-ep32"]
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"][:len(CELLS)])


def test_every_declared_metric_has_its_file_and_reader():
    mixes = {w["name"]: w["traffic"] for w in MANIFEST["workloads"]}
    declared = {m["name"]: m for m in MANIFEST["per_layer"]}
    seen = set()
    for mix in sorted(set(mixes.values())):
        for name, spec in harness.layer_metrics_for(mix).items():
            if name not in declared:
                continue            # a file kept as data (steps ahead)
            seen.add(name)
            m = declared[name]
            assert callable(harness.resolve(spec["reader"]))
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == m[key], (name, key)
            assert [mixes[w] for w in m["workloads"]] == [mix], name
    assert seen == set(declared)
    [e2e] = [m for m in MANIFEST["end_to_end"]
             if m["name"] == "serve_tokens_per_s"]
    assert e2e["workloads"][:4] == CELLS[1:]
    [entry] = [c for c in MANIFEST["configs"] if c["name"] == "glm-5-5l-ep32"]
    assert entry["source"].startswith(
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json")
    assert entry["reduced"] == harness.load_cell(CELLS[4])["config"]["reduced"]
