"""What ``test_manifest_appended.py``'s pinned case held, less its pin,
plus this PR's tail.

``test_manifest_appended.py::
test_what_came_later_is_appended_and_the_new_cells_alone`` (PR 31)
asserts that EXACTLY fifteen entries follow PR 30's in ``per_layer`` and
that all end in ``.longdoc``: true of the manifest PR 31 left, false of
any manifest a later PR appends to — and the driver takes new entries at
the end of the list only.  That file is the benchmark's and not a
``model_config`` PR's to edit, and ``tests/benchmark_tests/conftest.py``
exists already, so ``tests/conftest.py`` marks the case an expected
failure.  Here: PR 31's fifteen ``.longdoc`` entries still follow PR
30's twenty-nine, in their order and untouched, and what follows them
is this PR's cell's alone and ends in ``.longctx``.  The next
``benchmark`` issue drops both pins (PR 28's, PR 31's) and both xfails
(PERF.md section 7).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

from test_manifest_appended import ACCEPTED  # noqa: E402

MANIFEST = harness.load_manifest()

# per_layer's entries of PR 31, in its order
LONGDOC = [
    "decode_step_ms", "prefill_step_ms", "tpot_p90_ms", "device_idle_pct",
    "host_gap_ms", "dispatch_ms", "sched_plan_ms", "queue_wait_p90_ms",
    "step_rows_empty_pct", "experts_hit_pct", "expert_rows_max_over_mean",
    "expert_matmul_roofline_pct", "linear_attn_time_pct",
    "linear_attn_step_roofline_pct", "linear_attn_scan_roofline_pct"]


def test_pr31s_entries_follow_pr30s_in_order_and_untouched():
    later = MANIFEST["per_layer"][len(ACCEPTED):len(ACCEPTED) + 15]
    assert [m["name"] for m in later] == [n + ".longdoc" for n in LONGDOC]
    files = harness.layer_metrics_for("longdoc")
    for m in later:
        assert m["workloads"] == ["solar-open2-8l-ep32.longdoc"]
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for key in ("unit", "better", "source", "layer", "moves"):
            assert files[m["name"]][key] == m[key], (m["name"], key)


def test_what_follows_them_is_this_cells_alone():
    tail = MANIFEST["per_layer"][len(ACCEPTED) + 15:]
    assert len(tail) == 18
    for m in tail:
        assert m["name"].endswith(".longctx")
        assert m["workloads"] == ["glm-5-5l-ep32.longctx"]
        assert m["moves"] == "serve_tokens_per_s"
    # the cells and configurations too: appended, the accepted ones first
    assert [w["name"] for w in MANIFEST["workloads"]] == [
        "gpt3-760m.pretrain", "mistral-7b-8l.batch",
        "mimo-v2.5-7l-ep32.longgen", "solar-open2-8l-ep32.longdoc",
        "glm-5-5l-ep32.longctx"]
    assert [c["name"] for c in MANIFEST["configs"]][-2:] == [
        "solar-open2-8l-ep32", "glm-5-5l-ep32"]
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
