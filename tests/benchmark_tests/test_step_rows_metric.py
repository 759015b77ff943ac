"""``step_rows_empty_pct.*`` (``benchmark/layer_metrics/step_rows.py``):
the share of a window's packed token rows that carried no token, on
hand-made ``batch_step`` records, and nothing where the records carry no
``rows`` — as the records of a program that pads every lane to the widest
chunk do."""
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


def _step(q_width, tokens, rows=None, fused_steps=1):
    rec = {"kind": "batch_step", "q_width": q_width, "tokens": tokens,
           "prefill_seqs": int(q_width > 1), "fused_steps": fused_steps}
    if rows is not None:
        rec["rows"] = rows
    return rec


@pytest.mark.parametrize("steps,want", [
    # a 600-token prompt beside seven decoding lanes in 1,032 rows
    ([_step(1024, 607, 1032)], 100.0 * (1 - 607 / 1032)),
    # eight full decode steps beside it: 64 rows, all real
    ([_step(1024, 607, 1032)] + [_step(1, 8, 8)] * 8,
     100.0 * (1 - 671 / 1096)),
    # a full chunk and seven tokens leave one row of 1,032
    ([_step(1024, 1031, 1032)], 100.0 / 1032),
    # a fused window of four iterations over six live lanes of eight
    ([_step(1, 24, 32, fused_steps=4)], 25.0),
    # decode-only steps with every lane live: nothing empty
    ([_step(1, 8, 8)] * 3, 0.0),
])
def test_share_of_rows_that_carried_no_token(steps, want):
    got = step_rows.step_rows_empty_pct({"batch_steps": steps})
    assert got == pytest.approx(want)


def test_records_without_rows_read_as_nothing():
    """The parent commit's records: q_width and tokens, no rows."""
    old = [_step(1024, 607), _step(1, 8)]
    assert step_rows.step_rows_empty_pct({"batch_steps": old}) is None
    assert step_rows.step_rows_empty_pct({"batch_steps": []}) is None
    assert step_rows.step_rows_empty_pct({}) is None
    # a log that changes hands mid-window: only what carries the field
    mixed = old + [_step(512, 390, 520)]
    assert step_rows.step_rows_empty_pct({"batch_steps": mixed}) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("mix,cell", [
    ("batch", "mistral-7b-8l.batch"),
    ("longgen", "mimo-v2.5-7l-ep32.longgen")])
def test_metric_file_and_manifest_entry_agree(mix, cell):
    name = f"step_rows_empty_pct.{mix}"
    spec = harness.layer_metrics_for(mix)[name]
    assert harness.resolve(spec["reader"]) is step_rows.step_rows_empty_pct
    [entry] = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "Engine step",
                     "moves": "serve_tokens_per_s", "workloads": [cell]}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    # appended: nothing the manifest had moved
    assert [m["name"] for m in MANIFEST["per_layer"]][-2:] == [
        "step_rows_empty_pct.batch", "step_rows_empty_pct.longgen"]


def test_the_engines_records_carry_what_the_reader_reads():
    """``rows`` and ``prefill_waiting`` are in the event schema and the
    docs' table, so a record that carries them is a documented one."""
    from paddle_tpu.observability import events
    fields = events.EVENT_SCHEMA["batch_step"]
    assert fields["rows"] == "int" and fields["prefill_waiting"] == "int"
    with open(os.path.join(ROOT, "docs", "observability_events.md"),
              encoding="utf-8") as fh:
        doc = fh.read()
    assert "| `rows` | int" in doc and "| `prefill_waiting` | int" in doc
