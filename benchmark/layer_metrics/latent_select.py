"""Readers of what a model adds whose token mixer is latent attention
over the keys an index picks, behind a leading dense layer and then
expert layers: the share of a sequence's keys the selection keeps, the
share of the device's time the selection and the selected attention
take, the share of their roofline that three pieces reach, and the
expert layer's three metrics for a stack that says its expert layers
with ``first_k_dense_replace``.

**Which operations are whose.**  The program wraps the mixer in
``jax.named_scope("latent_attention")`` and inside it ``index_select``
(with ``index_score`` and ``index_topk`` under it) and
``sparse_attention`` (``models/generation.py``,
``ops/latent_select.py``); the expert loop runs under
``expert_matmul`` (``ops/routed_experts.py``).  A device trace keeps an
operation's scope in the ``tf_op`` stat of its event's metadata
(``layer_metrics/linear_attention.py``, whose ``XSpace`` messages and
interval arithmetic are imported here); the path names the program too,
so the decode-only program's operations (``serve_step_q1``, "narrow")
are told from a wider program's.  A ``while`` or ``conditional``
operation's event spans the events of its body, so seconds are the
union of the matching events' intervals, never their sum.

**What is counted.**  The counters ``keys_visible`` and
``keys_selected`` of a ``batch_step`` record are one latent layer's
worth, summed over the step's real rows: a row at position ``p`` sees
``p + 1`` keys and keeps ``min(index_topk, p + 1)``.  The rooflines
count the SELECTED work whatever implements it — a form that multiplies
every visible key under a mask reads low and can never read over 100 %:

* :func:`index_score_bytes_ops`: a row that sees more keys than the
  index keeps (``select_rows`` of them a step) scores every key it sees,
  ``2 x index_n_heads x index_head_dim`` operations a key — together
  they see ``keys_visible - keys_selected + index_topk x select_rows``
  keys; a row that keeps every key needs no score.  A decoding lane
  that chooses reads its sequence's index keys once (``index_head_dim``
  values a key).  In a step with a prefill chunk only the operations are
  counted: a chunk's rows share their sequence's keys, which the records
  do not count a sequence, and from 15 rows a sequence on the
  operations are the larger term;
* :func:`attend_bytes_ops`: a row's attention is ``2 x heads x
  (kv_lora_rank + qk_rope_head_dim + kv_lora_rank)`` operations a KEPT
  key (its logit over the 576-wide row, its weighted sum over the 512
  latent); a decoding lane reads each kept row of the pool once (640
  values as the pool is laid out).  In a step with a prefill chunk only
  the operations are counted, for the same reason: rows of one block
  share the keys they keep, so a gather's bytes a row are no floor.

A share is the mean roofline seconds a step of the stretch's records of
its kind (``max(bytes / HBM bandwidth, operations / bf16 peak)``,
``harness.DEVICE_PEAKS``, times the layers) over the scope's device
seconds a run of the programs of its kind.  On a TPU a trace without
such operations gives None.  A rehearsal on the CPU has no device
plane: the shares then divide by ``step_s`` at the v5e's peaks, to
exercise the arithmetic; such values mean nothing.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.layer_metrics import experts_window as ew
from benchmark.layer_metrics import linear_attention as la
from benchmark.layer_metrics import readers

SCOPES = ("latent_attention", "index_select", "index_score", "index_topk",
          "sparse_attention", "expert_matmul")


# ---------------------------------------------------------------------------
# what a step had to move and multiply (counted from the configuration)
# ---------------------------------------------------------------------------

def expert_layers(cfg: Dict[str, Any]) -> int:
    return int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])


def index_score_bytes_ops(cfg: Dict[str, Any], keys_visible: int,
                          keys_selected: int, select_rows: int,
                          decode_only: bool) -> Tuple[float, float]:
    """Bytes and operations of ONE layer's index scores of a step:
    ``select_rows`` of its rows chose among the keys they saw, and all
    its rows saw ``keys_visible`` keys and kept ``keys_selected``."""
    d = int(cfg["index_head_dim"])
    scored = keys_visible - keys_selected \
        + int(cfg["index_topk"]) * select_rows
    ops = 2.0 * scored * int(cfg["index_n_heads"]) * d
    nbytes = float(scored * d * ew._itemsize(cfg)) if decode_only else 0.0
    return nbytes, ops


def attend_bytes_ops(cfg: Dict[str, Any], keys_selected: int,
                     decode_only: bool) -> Tuple[float, float]:
    """Bytes and operations of ONE layer's attention of a step whose
    rows kept ``keys_selected`` keys in all."""
    rank, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    ops = 2.0 * keys_selected * int(cfg["num_attention_heads"]) \
        * (rank + rope + rank)
    nbytes = float(keys_selected * ew._pool_width(rank + rope)
                   * ew._itemsize(cfg)) if decode_only else 0.0
    return nbytes, ops


# ---------------------------------------------------------------------------
# what the trace holds
# ---------------------------------------------------------------------------

def read_scopes(serialized: bytes, scopes=SCOPES) -> Dict[str, Any]:
    """From a serialized ``XSpace``: for each of ``scopes`` the
    intervals (picoseconds) of the device planes' ``XLA Ops`` events
    whose ``tf_op`` path lies under it, in the decode-only program
    (``"narrow"``) and in the wider ones (``"wide"``), and the runs of
    each kind of program on the ``XLA Modules`` line."""
    space = la._xspace_class()()
    space.ParseFromString(serialized)
    out: Dict[str, Any] = {"narrow_runs": 0, "wide_runs": 0}
    for s in scopes:
        out[s] = {"narrow": [], "wide": []}
    for plane in space.planes:
        if not re.match(readers.DEVICE_PLANE, plane.name.decode()):
            continue
        stat_names = {e.key: e.value.name.decode()
                      for e in plane.stat_metadata}
        path, name = {}, {}
        for entry in plane.event_metadata:
            name[entry.key] = entry.value.name.decode(errors="replace")
            for stat in entry.value.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    path[entry.key] = \
                        stat.str_value.decode(errors="replace") \
                        or stat_names.get(stat.ref_value, "")
        for line in plane.lines:
            which = line.name.decode()
            if which == la.MODULES_LINE:
                for ev in line.events:
                    module = name.get(ev.metadata_id, "")
                    if la._PROGRAM.match(module):
                        narrow = la._NARROW_PROGRAM.match(module)
                        out["narrow_runs" if narrow else "wide_runs"] += 1
            if which != readers.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            for ev in line.events:
                p = path.get(ev.metadata_id, "")
                if not la._PROGRAM.match(p):
                    continue
                kind = "narrow" if la._NARROW_PROGRAM.match(p) else "wide"
                span = (t0 + ev.offset_ps,
                        t0 + ev.offset_ps + ev.duration_ps)
                for s in scopes:
                    if f"/{s}/" in p + "/":
                        out[s][kind].append(span)
    return out


def _observe(observed: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The trace's scopes reduced to seconds and runs, read once a traced
    run (kept under ``observed["latent_select"]``)."""
    if "latent_select" not in observed:
        path = la._trace_path(observed)
        seen = None
        if path:
            with open(path, "rb") as fh:
                raw = read_scopes(fh.read())
            seen = {"narrow_runs": raw["narrow_runs"],
                    "wide_runs": raw["wide_runs"]}
            for s in SCOPES:
                seen[s] = {
                    "narrow_s": la._union_s(raw[s]["narrow"]),
                    "wide_s": la._union_s(raw[s]["wide"]),
                    "all_s": la._union_s(raw[s]["narrow"] + raw[s]["wide"])}
            print(f"trace: latent attention and experts: {seen}", flush=True)
        observed["latent_select"] = seen
    return observed["latent_select"]


def _stretch_steps(observed: Dict[str, Any], field: str, kind: str
                   ) -> List[Dict[str, Any]]:
    """The traced stretch's warm records that carry ``field``, of the
    decode-only program (``"narrow"``), of the wider ones (``"wide"``)
    or of both (``"all"``)."""
    lo, hi = observed.get("traced_wall", (float("-inf"), float("inf")))
    keep = {"narrow": lambda q: q <= 1, "wide": lambda q: q > 1,
            "all": lambda q: True}[kind]
    return [s for s in observed.get("batch_steps") or ()
            if field in s and lo <= s["ts"] <= hi and keep(s["q_width"])]


def _roofline_pct(observed: Dict[str, Any], scope: str, kind: str,
                  field: str, bytes_ops, layers: int) -> Optional[float]:
    steps = _stretch_steps(observed, field, kind)
    if not steps or "config" not in observed or not layers:
        return None
    cfg = observed["config"]
    if la._on_chip(observed):
        seen = _observe(observed)
        runs = 0 if not seen else (
            seen["narrow_runs"] + seen["wide_runs"] if kind == "all"
            else seen[kind + "_runs"])
        if not runs or not seen[scope][kind + "_s"]:
            return None
        peaks = harness.peaks_for(observed["device_kind"])
        device_s = seen[scope][kind + "_s"] / runs
    else:
        # a rehearsal on the CPU: no device plane to read a scope from
        peaks = harness.DEVICE_PEAKS["TPU v5 lite"]
        device_s = harness.median([s["step_s"] for s in steps])
    total = 0.0
    for s in steps:
        nbytes, ops = bytes_ops(cfg, s)
        total += layers * max(nbytes / peaks["hbm_bytes_per_s"],
                              ops / peaks["bf16_flops"])
    if not total or not device_s:
        return None
    return 100.0 * (total / len(steps)) / device_s


# ---------------------------------------------------------------------------
# readers: the selection
# ---------------------------------------------------------------------------

def keys_selected_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = [s for s in observed.get("batch_steps") or ()
             if "keys_visible" in s]
    seen = sum(s["keys_visible"] for s in steps)
    if not seen:
        return None
    return 100.0 * sum(s["keys_selected"] for s in steps) / seen


def _time_pct(observed: Dict[str, Any], scope: str, field: str,
              bytes_ops) -> Optional[float]:
    if "config" not in observed or not observed.get("trace"):
        return None
    if not la._on_chip(observed):
        # a rehearsal: the scope's roofline seconds over the steps'
        return _roofline_pct(observed, scope, "all", field, bytes_ops,
                             int(observed["config"]["num_hidden_layers"]))
    seen = _observe(observed)
    if not seen or not seen[scope]["all_s"]:
        return None
    return 100.0 * seen[scope]["all_s"] / observed["trace"]["busy_s"]


def _score(cfg, s):
    return index_score_bytes_ops(cfg, s["keys_visible"], s["keys_selected"],
                                 s["select_rows"], s["q_width"] <= 1)


def _attend(cfg, s):
    return attend_bytes_ops(cfg, s["keys_selected"], s["q_width"] <= 1)


def index_select_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _time_pct(observed, "index_select", "keys_visible", _score)


def sparse_attn_time_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _time_pct(observed, "sparse_attention", "keys_selected", _attend)


def index_score_roofline_pct(observed: Dict[str, Any]) -> Optional[float]:
    cfg = observed.get("config") or {}
    return _roofline_pct(observed, "index_score", "all", "keys_visible",
                         _score, int(cfg.get("num_hidden_layers", 0)))


def sparse_attn_decode_roofline_pct(observed: Dict[str, Any]
                                    ) -> Optional[float]:
    cfg = observed.get("config") or {}
    return _roofline_pct(observed, "sparse_attention", "narrow",
                         "keys_selected", _attend,
                         int(cfg.get("num_hidden_layers", 0)))


def sparse_attn_prefill_roofline_pct(observed: Dict[str, Any]
                                     ) -> Optional[float]:
    cfg = observed.get("config") or {}
    return _roofline_pct(observed, "sparse_attention", "wide",
                         "keys_selected", _attend,
                         int(cfg.get("num_hidden_layers", 0)))


# ---------------------------------------------------------------------------
# readers: the expert layer of a stack with leading dense layers
# ---------------------------------------------------------------------------

def _held(cfg: Dict[str, Any]) -> int:
    """Held experts summed over the expert layers."""
    return int(cfg["n_routed_experts"]) * expert_layers(cfg)


def expert_rows_max_over_mean(observed: Dict[str, Any]) -> Optional[float]:
    steps = ew._decode_steps(observed, "expert_rows_max")
    rows = sum(s["expert_rows"] for s in steps)
    if not rows or "config" not in observed:
        return None
    return sum(s["expert_rows_max"] for s in steps) \
        / (rows / _held(observed["config"]))


def experts_hit_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = ew._decode_steps(observed, "experts_hit")
    if not steps or "config" not in observed:
        return None
    return 100.0 * sum(s["experts_hit"] for s in steps) \
        / (len(steps) * _held(observed["config"]))


def expert_matmul_roofline_pct(observed: Dict[str, Any]) -> Optional[float]:
    """Roofline seconds a decode-only step of the held experts that were
    hit (``experts_window.expert_bytes_ops``; the records' counts are
    summed over the expert layers already) over the device seconds a
    run of the decode-only program under ``expert_matmul``."""
    return _roofline_pct(
        observed, "expert_matmul", "narrow", "experts_hit",
        lambda cfg, s: ew.expert_bytes_ops(cfg, s["experts_hit"],
                                           s["expert_rows"]), 1)
