"""The expert layer's three metrics for a configuration whose every
layer is an expert layer and that says so with ``num_hidden_layers``
(``experts_window.py`` counts a model's expert layers from MiMo-V2's
``moe_layer_freq``, which such a file does not have).  The counters, the
traced kernels (``observed["kernel_s"]``, which the runner fills through
``experts_window.observe_kernels``) and the bytes and operations of an
expert (``experts_window.expert_bytes_ops``) are that module's; only the
count of layers differs, and that a step is counted by the attention
kernel of the ``gqa_layers``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark import harness
from benchmark.layer_metrics import experts_window as ew


def _held(cfg: Dict[str, Any]) -> int:
    """Held experts summed over the layers."""
    return int(cfg["n_routed_experts"]) * int(cfg["num_hidden_layers"])


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
    """Roofline seconds a narrow step of the held experts that were hit
    over the device seconds a narrow step of the expert loop's
    ``conditional`` operations."""
    lo, hi = observed.get("traced_wall", (float("-inf"), float("inf")))
    steps = [s for s in observed.get("batch_steps") or ()
             if "experts_hit" in s and s["q_width"] <= ew._NARROW_Q
             and lo <= s["ts"] <= hi]
    if not steps or "config" not in observed:
        return None
    cfg = observed["config"]
    if str(observed.get("device_kind", "")).startswith("TPU"):
        kernels = observed.get("kernel_s") or {}
        seen, att = kernels.get("expert"), kernels.get("full")
        if not (seen and seen["narrow_n"] and att and att["narrow_n"]):
            return None
        peaks = harness.peaks_for(observed["device_kind"])
        n_steps = att["narrow_n"] / len(cfg["gqa_layers"])
        device_s = seen["narrow_s"] / n_steps
    else:
        # a rehearsal on the CPU: no operation of the kernel's name
        peaks = harness.DEVICE_PEAKS["TPU v5 lite"]
        device_s = harness.median([s["step_s"] for s in steps])
    total = 0.0
    for s in steps:
        nbytes, ops = ew.expert_bytes_ops(cfg, s["experts_hit"],
                                          s["expert_rows"])
        total += max(nbytes / peaks["hbm_bytes_per_s"],
                     ops / peaks["bf16_flops"])
    if not total or not device_s:
        return None
    return 100.0 * (total / len(steps)) / device_s
