"""What ``test_step_rows_metric.py``'s pinned-tail cases held, less the
pin (see ``conftest.py``): each ``step_rows_empty_pct.*`` file agrees
with its manifest entry, and ``per_layer`` keeps the accepted names
first and in their order, with a later PR's entries after them."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.layer_metrics import step_rows  # noqa: E402

MANIFEST = harness.load_manifest()

# per_layer as PR 30 left it, in its order
ACCEPTED = [
    "train_step_ms", "train_mfu_pct", "padded_rows_pct.batch",
    "tpot_p90_ms.batch", "prefill_step_ms.batch", "decode_step_ms.batch",
    "device_idle_pct.train", "device_idle_pct.batch", "host_gap_ms.batch",
    "dispatch_ms.batch", "sched_plan_ms.batch", "queue_wait_p90_ms.batch",
    "decode_step_ms.longgen", "prefill_step_ms.longgen",
    "padded_rows_pct.longgen", "tpot_p90_ms.longgen",
    "device_idle_pct.longgen", "host_gap_ms.longgen",
    "expert_rows_max_over_mean.longgen", "experts_hit_pct.longgen",
    "window_pages_read_pct.longgen",
    "ragged_attn_window_roofline_pct.longgen",
    "ragged_attn_full_roofline_pct.longgen",
    "expert_matmul_roofline_pct.longgen", "dispatch_ms.longgen",
    "sched_plan_ms.longgen", "queue_wait_p90_ms.longgen",
    "step_rows_empty_pct.batch", "step_rows_empty_pct.longgen"]


@pytest.mark.parametrize("mix,cell", [
    ("batch", "mistral-7b-8l.batch"),
    ("longgen", "mimo-v2.5-7l-ep32.longgen"),
    ("longdoc", "solar-open2-8l-ep32.longdoc")])
def test_step_rows_file_and_manifest_entry_agree(mix, cell):
    name = f"step_rows_empty_pct.{mix}"
    spec = harness.layer_metrics_for(mix)[name]
    assert harness.resolve(spec["reader"]) is step_rows.step_rows_empty_pct
    [entry] = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "Engine step",
                     "moves": "serve_tokens_per_s", "workloads": [cell]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]


def test_accepted_entries_come_first_and_in_their_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED


def test_what_came_later_is_appended_and_the_new_cells_alone():
    later = MANIFEST["per_layer"][len(ACCEPTED):]
    assert len(later) == 15
    for m in later:
        assert m["name"].endswith(".longdoc")
        assert m["workloads"] == ["solar-open2-8l-ep32.longdoc"]
        assert m["moves"] == "serve_tokens_per_s"
