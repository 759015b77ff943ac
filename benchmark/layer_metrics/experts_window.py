"""Readers of what a model with expert layers and window attention
layers adds to the program: three counters of ``batch_step`` and one
share of its roofline for each of three kernels.

**Counters** (``observed["batch_steps"]``, the window's warm records;
decode-only steps, ``prefill_seqs = 0``): ``expert_rows`` (rows routed
to the held experts, summed over expert layers), ``expert_rows_max``
(the fullest held expert's rows, the maximum over layers),
``experts_hit`` (held experts with a row, summed over layers),
``window_pages_read`` and ``full_pages_read`` (KV pages the window and
the full layers' kernels were asked to visit, summed over lanes and
layers).  Each metric is a ratio of sums over the window, so a step
with no local row divides nothing by zero.  A record without the
fields, as a program without them writes, reads as nothing: None.

**Roofline shares** (``observed["kernel_s"]``, which
:func:`observe_kernels` fills from the traced run's ``.xplane.pb``).
Over the **narrow steps** of the traced stretch — ``q_width <= 8``: the
decode steps, whose kernels the trace tells apart by their output's
query axis of 8 — a share is

    roofline seconds a step / device seconds a step

where the device seconds are the summed durations of the kernel's
operations over the number of such steps in the trace (an attention
kernel's operations / layers of its kind), and the roofline seconds are the mean over the
stretch's ``batch_step`` records of ``max(bytes / HBM bandwidth,
operations / bf16 peak)`` from ``harness.DEVICE_PEAKS``.  Bytes and
operations are counted from the records and the configuration by
:func:`attention_bytes_ops` and :func:`expert_bytes_ops` below, and
only what the kernel cannot avoid is counted (the pages visited, the
weights of the experts hit; not q, the output or the page tables), so a
share over 100 % would be a wrong count here and not a fast kernel.

A rehearsal on the CPU (``observed["device_kind"]`` is no TPU) has no
operation of these names (interpret mode runs the kernel as plain XLA):
the reader then divides by the narrow steps' ``step_s`` and takes the
v5e's row of the table, to exercise the arithmetic.  Such a value means
nothing.  On a TPU a trace without the kernel's operations gives None:
the metric falls silent and is never computed from host time there.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

from benchmark import harness
from benchmark.layer_metrics import readers

# an operation's own name in the trace (the instruction's, without its
# number) -> the key under observed["kernel_s"].  The Pallas calls are
# named by jax.named_scope (ops/pallas/ragged_paged_attention.py).  The
# expert loop (ops/routed_experts.py, scope expert_matmul) is XLA's own
# operations, whose scope a device trace does not keep: its time is that
# of the step's ``conditional`` operations, one a held expert a layer,
# each spanning the loop over that expert's tiles where it has a row.
# Only a conditional whose output is the layer's [rows, hidden] counts
_KERNELS = (("ragged_paged_attn_window", "window"),
            ("ragged_paged_attn", "full"),
            ("conditional", "expert"))
_NARROW_Q = 8
_OWN_NAME = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = \(?\w+\[([\d,]*)\]")


# ---------------------------------------------------------------------------
# what the trace holds
# ---------------------------------------------------------------------------

def observe_kernels(xplane_path: Optional[str], observed: Dict[str, Any]
                    ) -> None:
    """Sum the device's operation seconds by kernel into
    ``observed["kernel_s"][kernel] = {"narrow_s", "narrow_n", "all_s",
    "all_n"}``: seconds and operations of the narrow steps (an attention
    kernel whose output's query axis is at most ``_NARROW_Q``; an expert
    operation is narrow when the step around it is, told by the
    attention kernel before it) and of all steps.  Nothing is written
    where the trace holds no such operation."""
    if not xplane_path:
        return
    hidden = str((observed.get("config") or {}).get("hidden_size"))
    out: Dict[str, Dict[str, float]] = {}
    for events in readers.read_xplane(xplane_path)["device"].values():
        narrow = False
        for name, _, duration_ns in sorted(events, key=lambda e: e[1]):
            m = _OWN_NAME.match(name)
            kernel = next((key for own, key in _KERNELS
                           if m and m[1] == own), None)
            if kernel is None:
                continue
            dims = m[2].split(",")
            if kernel == "expert":
                if len(dims) != 2 or dims[1] != hidden:
                    continue
            else:
                narrow = len(dims) == 4 and int(dims[2]) <= _NARROW_Q
            acc = out.setdefault(kernel, {"narrow_s": 0.0, "narrow_n": 0,
                                          "all_s": 0.0, "all_n": 0})
            acc["all_s"] += duration_ns / 1e9
            acc["all_n"] += 1
            if narrow:
                acc["narrow_s"] += duration_ns / 1e9
                acc["narrow_n"] += 1
    if out:
        observed["kernel_s"] = out
        for k, v in sorted(out.items()):
            print(f"trace: kernel {k}: {v['all_s']:.4f} s in {v['all_n']} "
                  f"operations, of which narrow steps {v['narrow_s']:.4f} s "
                  f"in {v['narrow_n']}", flush=True)


# ---------------------------------------------------------------------------
# what a step had to move and multiply (counted from the configuration)
# ---------------------------------------------------------------------------

def _layers(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """(window layers, full layers, expert layers)."""
    n_window = sum(1 for w in cfg["hybrid_layer_pattern"] if w)
    return (n_window, len(cfg["hybrid_layer_pattern"]) - n_window,
            sum(1 for e in cfg["moe_layer_freq"] if e))


def _itemsize(cfg: Dict[str, Any]) -> int:
    return {"float32": 4, "bfloat16": 2}[cfg["serve"]["dtype"]]


def _pool_width(dim: int) -> int:
    """The pools' last axis as the program lays it out: a head wider
    than 128 lanes is padded to whole tiles."""
    return dim if dim <= 128 else -(-dim // 128) * 128


def attention_bytes_ops(cfg: Dict[str, Any], pages: int, window: bool
                        ) -> Tuple[float, float]:
    """Bytes and operations of the attention kernels of one kind over
    ``pages`` visits (``window_pages_read`` or ``full_pages_read``, lanes
    and layers already summed) in a step of one query row a lane: every
    visit reads the page's keys and values for every kv head, and every
    query head multiplies its row with the page's keys and its
    probabilities with the page's values."""
    ps = int(cfg["serve"]["page_size"])
    kv = int(cfg["swa_num_key_value_heads" if window
                 else "num_key_value_heads"])
    dk, dv = int(cfg["head_dim"]), int(cfg["v_head_dim"])
    nbytes = pages * ps * kv * (_pool_width(dk) + _pool_width(dv)) \
        * _itemsize(cfg)
    ops = pages * ps * int(cfg["num_attention_heads"]) * 2 * (dk + dv)
    return float(nbytes), float(ops)


def expert_bytes_ops(cfg: Dict[str, Any], experts_hit: int,
                     expert_rows: int) -> Tuple[float, float]:
    """Bytes and operations of the expert loop of one step: the three
    matrices of every held expert a row picked (``experts_hit``, summed
    over layers), and the three products of every routed row
    (``expert_rows``)."""
    one = 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])
    return float(experts_hit * one * _itemsize(cfg)), \
        float(expert_rows * 2 * one)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def _decode_steps(observed: Dict[str, Any], field: str
                  ) -> List[Dict[str, Any]]:
    return [s for s in observed.get("batch_steps") or ()
            if field in s and s["prefill_seqs"] == 0]


def expert_rows_max_over_mean(observed: Dict[str, Any]) -> Optional[float]:
    steps = _decode_steps(observed, "expert_rows_max")
    rows = sum(s["expert_rows"] for s in steps)
    if not rows or "config" not in observed:
        return None
    cfg = observed["config"]
    held = int(cfg["n_routed_experts"]) * _layers(cfg)[2]
    return sum(s["expert_rows_max"] for s in steps) / (rows / held)


def experts_hit_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = _decode_steps(observed, "experts_hit")
    if not steps or "config" not in observed:
        return None
    cfg = observed["config"]
    held = int(cfg["n_routed_experts"]) * _layers(cfg)[2]
    return 100.0 * sum(s["experts_hit"] for s in steps) \
        / (len(steps) * held)


def window_pages_read_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = _decode_steps(observed, "window_pages_read")
    full = sum(s["full_pages_read"] for s in steps)
    if not full or "config" not in observed:
        return None
    n_window, n_full, _ = _layers(observed["config"])
    if not n_window or not n_full:
        return None
    return 100.0 * sum(s["window_pages_read"] for s in steps) \
        / (full * n_window / n_full)


# ---------------------------------------------------------------------------
# roofline shares
# ---------------------------------------------------------------------------

def _roofline_pct(observed: Dict[str, Any], kernel: str) -> Optional[float]:
    field = {"window": "window_pages_read", "full": "full_pages_read",
             "expert": "experts_hit"}[kernel]
    lo, hi = observed.get("traced_wall", (float("-inf"), float("inf")))
    steps = [s for s in observed.get("batch_steps") or ()
             if field in s and s["q_width"] <= _NARROW_Q
             and lo <= s["ts"] <= hi]
    if not steps or "config" not in observed:
        return None
    cfg = observed["config"]
    n_window, n_full, n_expert = _layers(cfg)
    per_step = {"window": n_window, "full": n_full}.get(kernel)
    seen = (observed.get("kernel_s") or {}).get(kernel)
    on_chip = str(observed.get("device_kind", "")).startswith("TPU")
    if on_chip and not (seen and seen["narrow_n"]
                        and (per_step or kernel == "expert")):
        return None
    if on_chip:
        peaks = harness.peaks_for(observed["device_kind"])
        if kernel == "expert":
            # the loop's operations a step vary with the routing: the
            # steps in the trace are counted by the attention kernels
            att = (observed["kernel_s"].get("window")
                   or observed["kernel_s"].get("full"))
            n_steps = att["narrow_n"] / (n_window or n_full)
        else:
            n_steps = seen["narrow_n"] / per_step
        device_s = seen["narrow_s"] / n_steps
    else:
        # a rehearsal on the CPU: no operation of the kernel's name
        peaks = harness.DEVICE_PEAKS["TPU v5 lite"]
        device_s = harness.median([s["step_s"] for s in steps])
    total = 0.0
    for s in steps:
        if kernel == "expert":
            nbytes, ops = expert_bytes_ops(cfg, s["experts_hit"],
                                           s["expert_rows"])
        else:
            nbytes, ops = attention_bytes_ops(cfg, s[field],
                                              kernel == "window")
        total += max(nbytes / peaks["hbm_bytes_per_s"],
                     ops / peaks["bf16_flops"])
    if not total or not device_s:
        return None
    return 100.0 * (total / len(steps)) / device_s


def ragged_attn_window_roofline_pct(observed: Dict[str, Any]
                                    ) -> Optional[float]:
    return _roofline_pct(observed, "window")


def ragged_attn_full_roofline_pct(observed: Dict[str, Any]
                                  ) -> Optional[float]:
    return _roofline_pct(observed, "full")


def expert_matmul_roofline_pct(observed: Dict[str, Any]) -> Optional[float]:
    return _roofline_pct(observed, "expert")
