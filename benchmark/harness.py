"""What every cell shares: finding a cell's files by name, the set-up
clock, the arithmetic of the end-to-end metrics, the device table and
the result line.

Nothing here imports jax or the program at module level: ``run.py``
decides the platform (``--rehearse``) before either is loaded, and the
tests import this module for its arithmetic alone.

Data is found by name and never by a list kept in code::

    cells/<cell>.json            -> "config", "traffic"
    configs/<config>.json        -> "builder" (builders/<name>.py, which
                                    binds the plain reference), sizes
    traffic/<mix>.json           -> "runner", distributions, loop
    layer_metrics/<metric>.json  -> "traffic" (the mix it applies to),
                                    "reader" ("module:function")

A file with a key that is not listed below is an error, and so is a
name that has no file: a default would hide a typing mistake in a file
that a later PR may add and never edit.
"""
from __future__ import annotations

import importlib
import json
import math
import os
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# Published peaks of one chip, keyed by jax's ``device_kind``.  A copy,
# not an import from paddle_tpu.device: later PRs may change the
# program and may not change the yardstick.  A kind that is not here is
# an error, never a default.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.  jax reports the
# v5e as "TPU v5 lite" (and "TPU v5e" in some releases).
_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud docs, 'TPU v5e': 197 TFLOP/s bf16, "
                  "819 GB/s, 16 GB"}
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {"TPU v5 lite": _V5E,
                                           "TPU v5e": _V5E}

_CELL_KEYS = {"config", "traffic", "chips", "why", "end_to_end", "rate"}
# a configuration file holds these and its builder's MODEL_KEYS (sizes)
_CONFIG_KEYS = {"source", "builder", "reduced", "assumed", "precision",
                "train", "serve", "rehearse", "notes"}
_TRAFFIC_KEYS = {"runner", "why", "loop", "clients", "pool", "prompt",
                 "output", "drain_s", "batch", "seq", "n_batches",
                 "steps_in_flight", "rehearse"}
_LAYER_METRIC_KEYS = {"layer", "unit", "better", "source", "moves",
                      "traffic", "reader", "what"}


class BenchmarkError(Exception):
    """A fault of the benchmark's own files or of a run; ends the run
    with a non-zero exit code and no result line."""


def peaks_for(device_kind: str) -> Dict[str, Any]:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmark/harness.py "
            f"DEVICE_PEAKS ({sorted(DEVICE_PEAKS)}); add its published "
            f"peaks with their source") from None


# ---------------------------------------------------------------------------
# files by name
# ---------------------------------------------------------------------------

def builder_for(config: Dict[str, Any]):
    """``benchmark/builders/<name>.py``: builds the program's model from
    the file's sizes and binds the family's plain reference."""
    try:
        return importlib.import_module(
            "benchmark.builders." + config["builder"])
    except ImportError as e:
        raise BenchmarkError(f"no builder {config['builder']!r}: {e}") \
            from e


def _load(kind: str, name: str, allowed: Optional[Iterable[str]]
          ) -> Dict[str, Any]:
    path = os.path.join(BENCH_DIR, kind, name + ".json")
    if not os.path.isfile(path):
        have = sorted(f[:-5] for f in os.listdir(os.path.join(
            BENCH_DIR, kind)) if f.endswith(".json"))
        raise BenchmarkError(f"no benchmark/{kind}/{name}.json "
                             f"(there: {have})")
    with open(path, encoding="utf-8") as fh:
        data = json.load(fh)
    if kind == "configs":
        allowed = _CONFIG_KEYS | set(builder_for(data).MODEL_KEYS)
    unknown = sorted(set(data) - set(allowed))
    if unknown:
        raise BenchmarkError(f"benchmark/{kind}/{name}.json has keys the "
                             f"harness does not know: {unknown}")
    return data


def load_manifest() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _overlay(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``, nested groups merged key by key."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _overlay(out[k], v)
        else:
            out[k] = v
    return out


def load_cell(name: str, rehearse: bool = False) -> Dict[str, Any]:
    """The cell's own file with its configuration and traffic files
    resolved.  ``rehearse`` lays each file's ``rehearse`` group (tiny
    sizes for the CPU) over it."""
    cell = _load("cells", name, _CELL_KEYS)
    for key in ("config", "traffic", "chips", "why", "end_to_end"):
        if key not in cell:
            raise BenchmarkError(f"cells/{name}.json lacks {key!r}")
    config = _load("configs", cell["config"], None)
    traffic = _load("traffic", cell["traffic"], _TRAFFIC_KEYS)
    if rehearse:
        config = _overlay(config, config.get("rehearse", {}))
        traffic = _overlay(traffic, traffic.get("rehearse", {}))
    config.pop("rehearse", None)
    traffic.pop("rehearse", None)
    return {"name": name, **cell, "config_name": cell["config"],
            "traffic_name": cell["traffic"], "config": config,
            "traffic": traffic}


def layer_metrics_for(traffic_name: str) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric whose file names this traffic mix.  A
    metric selects its cells by mix, so a new cell edits no metric's
    file and a new metric no cell's."""
    out = {}
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if not fname.endswith(".json"):
            continue
        spec = _load("layer_metrics", fname[:-5], _LAYER_METRIC_KEYS)
        if spec["traffic"] == traffic_name:
            out[fname[:-5]] = spec
    return out


def resolve(spec: str):
    """``"module:function"`` under the ``benchmark`` package."""
    mod, _, fn = spec.partition(":")
    try:
        return getattr(importlib.import_module("benchmark." + mod), fn)
    except (ImportError, AttributeError) as e:
        raise BenchmarkError(f"cannot resolve {spec!r}: {e}") from e


def read_layer_metrics(traffic_name: str, observed: Dict[str, Any]
                       ) -> Dict[str, Dict[str, Any]]:
    """Run each metric's reader over what the traced run observed.  A
    reader that finds nothing to read returns None and its metric is
    left out of the line."""
    out = {}
    for name, spec in layer_metrics_for(traffic_name).items():
        value = resolve(spec["reader"])(observed)
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_device(chips: int, rehearse: bool) -> Dict[str, Any]:
    """The device as jax reports it.  Without ``--rehearse`` anything
    but a TPU with enough chips ends the run before any result."""
    import jax
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise BenchmarkError(f"needs platform {want!r}, jax reports "
                             f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, jax reports "
                             f"{len(devs)}")
    if not rehearse:
        peaks_for(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int = 1) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it, as the CPU)."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts XLA backend compilations through jax.monitoring, so that a
    window that compiled is caught whatever program did it (``count``:
    every program handed to the backend, served from the persistent
    cache or not; ``cache_hits``: those that were)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self._EVENT:
            self.count += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# arithmetic of the end-to-end metrics (tests check these by hand)
# ---------------------------------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest order statistics (numpy's default), written out so
    that it cannot change with a library."""
    if not values:
        raise BenchmarkError("percentile of no samples")
    s = sorted(float(v) for v in values)
    k = (len(s) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def tpot_ms(token_times: Sequence[float]) -> Optional[float]:
    """Time per output token of one request, in ms: (last - first token
    arrival) / (tokens - 1).  None for a request of fewer than two
    tokens, which has no gap."""
    if len(token_times) < 2:
        return None
    return (token_times[-1] - token_times[0]) / (len(token_times) - 1) * 1e3


def padded_rows_pct(steps: Sequence[Dict[str, Any]], max_batch: int
                    ) -> Optional[float]:
    """Share of the rows a ragged step computed that carried no token:
    1 - sum(tokens) / sum(max_batch * q_width), over ``batch_step``
    events.  Every step runs all ``max_batch`` lanes at the widest
    lane's bucket."""
    rows = sum(int(max_batch) * int(s["q_width"]) for s in steps)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(int(s["tokens"]) for s in steps) / rows)


# ---------------------------------------------------------------------------
# the clock and the result line
# ---------------------------------------------------------------------------

class SetupClock:
    """``setup_s`` runs from the start of the process (``run.py`` takes
    the time before it imports anything heavy) to the window's start."""

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else t0
        self.setup_s: Optional[float] = None

    def mark(self, what: str) -> None:
        """A named point of set-up, printed on an earlier line so that
        what set-up spends is visible without a profiler."""
        print(f"setup: {time.perf_counter() - self.t0:8.2f} s  {what}",
              flush=True)

    def window_starts(self) -> float:
        self.setup_s = time.perf_counter() - self.t0
        print(f"setup: {self.setup_s:8.2f} s  window starts", flush=True)
        return self.setup_s


def check(ok: bool, what: str, failures: List[str]) -> bool:
    """One line per check; a failed one makes the run incorrect."""
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        failures.append(what)
    return bool(ok)


def result_line(cell: Dict[str, Any], trace: bool, *, correct: bool,
                attempted: int, failed: int,
                metrics: Dict[str, tuple],
                layer_metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The contract's last line.  With ``--trace 0`` the end-to-end
    metrics the cell's file lists, out of what the runner measured
    (``name -> (value, unit)``); with ``--trace 1`` its per-layer
    metrics."""
    if trace:
        shown = layer_metrics
    else:
        missing = sorted(set(cell["end_to_end"]) - set(metrics))
        if missing:
            raise BenchmarkError(f"the runner did not measure {missing}")
        shown = {n: {"value": float(metrics[n][0]), "unit": metrics[n][1]}
                 for n in cell["end_to_end"]}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": shown, "device": device}
    if trace and breakdown:
        line["breakdown"] = breakdown
    return json.dumps(line)


# ---------------------------------------------------------------------------
# the profiler, for --trace 1
# ---------------------------------------------------------------------------

class Profiler:
    """``jax.profiler`` around a short stretch of the window.  The
    Python tracer is off: it would write an event for every Python call
    of the engine's loop and the clients."""

    def __init__(self, out_dir: str):
        self.dir = os.path.join(out_dir, "trace")
        self.running = False

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.running = True

    def stop(self) -> None:
        import jax
        if self.running:
            jax.profiler.stop_trace()
            self.running = False

    def newest_xplane(self) -> Optional[str]:
        found = []
        for base, _, files in os.walk(self.dir):
            found.extend(os.path.join(base, f) for f in files
                         if f.endswith(".xplane.pb"))
        return max(found, key=os.path.getmtime) if found else None


def traced_device(profiler: "Profiler", observed: Dict[str, Any],
                  rehearse: bool) -> Dict[str, float]:
    """Reduce the traced stretch: prints every plane's and line's name
    (so that a wrong guess of the device plane shows in the output),
    puts the reduction under ``observed["trace"]`` and returns the
    ``busy_s`` and ``window_s`` that the result line's ``device``
    carries.  A rehearsal has no device plane: the host's events stand
    in, to exercise the same code, and mean nothing."""
    from benchmark.layer_metrics import readers
    path = profiler.newest_xplane()
    if path is None:
        raise BenchmarkError(f"the profiler wrote no .xplane.pb under "
                             f"{profiler.dir}")
    planes = readers.read_xplane(path)
    for plane, lines in planes["names"]:
        print(f"trace plane {plane!r}: lines {lines}", flush=True)
    if rehearse and not any(planes["device"].values()):
        planes["device"] = {"rehearsal stand-in": [
            ev for _, ev in planes["host"]]}
    reduced = readers.reduce_trace(planes)
    if reduced is None:
        raise BenchmarkError(
            f"no operation on a device plane ({readers.DEVICE_PLANE}, line "
            f"{readers.OPS_LINE!r}) in {path}")
    observed["trace"] = reduced
    print(f"trace: {path}: busy {reduced['busy_s']:.4f} s of "
          f"{reduced['window_s']:.4f} s", flush=True)
    return {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}


def breakdown_of(observed: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    t = observed.get("trace")
    if not t:
        return None
    return {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
