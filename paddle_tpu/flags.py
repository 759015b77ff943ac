"""Global flag registry with env ingestion.

TPU-native re-design of the reference's three-tier flag system
(ref: paddle/phi/core/flags.cc — PHI_DEFINE_EXPORTED_*; python
paddle.set_flags/get_flags).  Here a single Python registry holds typed
flags, ingests ``FLAGS_*`` environment variables at import, and exposes
``set_flags``/``get_flags`` with the same call signatures as the reference.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Union


@dataclass
class _Flag:
    name: str
    default: Any
    type: type
    help: str
    value: Any = None
    on_change: Optional[Callable[[Any], None]] = None


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(ftype: type, raw: Any) -> Any:
    if isinstance(raw, str) and ftype is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return ftype(raw)


def define_flag(name: str, default: Any, help: str = "",
                on_change: Optional[Callable[[Any], None]] = None) -> None:
    """Register a flag. ``name`` may be given with or without the FLAGS_ prefix."""
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    ftype = type(default)
    flag = _Flag(name=name, default=default, type=ftype, help=help,
                 on_change=on_change)
    env = os.environ.get(name)
    flag.value = _coerce(ftype, env) if env is not None else default
    _REGISTRY[name] = flag
    if env is not None and on_change is not None:
        try:
            on_change(flag.value)   # env override takes effect at import
        except Exception as e:      # a typo'd env var must not brick import
            import warnings
            warnings.warn(f"ignoring invalid {name}={env!r}: {e}")
            flag.value = default


def get_flags(flags: Union[str, Iterable[str], None] = None) -> Dict[str, Any]:
    """Query flag values. Mirrors ``paddle.get_flags``."""
    if flags is None:
        names: List[str] = list(_REGISTRY)
    elif isinstance(flags, str):
        names = [flags]
    else:
        names = list(flags)
    out = {}
    for n in names:
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict[str, Any]) -> None:
    """Set flag values. Mirrors ``paddle.set_flags``."""
    for n, v in flags.items():
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        f = _REGISTRY[key]
        new = _coerce(f.type, v)
        # validate via on_change BEFORE committing: a rejected value
        # must not leave the registry diverged from actual behavior
        if f.on_change is not None:
            f.on_change(new)
        f.value = new


def get_flag(name: str) -> Any:
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _REGISTRY[key].value


# ---------------------------------------------------------------------------
# Core flags (subset of the reference's ~300, the ones with behavioral effect
# here; more are registered where their subsystem lives).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False, "scan op outputs for nan/inf")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; >0: log only")
define_flag("benchmark", False, "synchronize (block_until_ready) after every op")
define_flag("sync_nccl_allreduce", False, "synchronize after every collective")
define_flag("seed", 0, "global random seed")
define_flag("use_stride_kernel", True, "accepted for API parity; XLA manages layout")
define_flag("eager_delete_tensor_gb", 0.0, "accepted for API parity; PJRT manages memory")
define_flag("allocator_strategy", "auto_growth", "accepted for API parity")
define_flag("fraction_of_gpu_memory_to_use", 0.92, "accepted for API parity")
define_flag("use_pallas_attention", True,
            "route attention through the Pallas flash kernel on TPU")
define_flag("use_pallas_softmax_ce", True,
            "route hard-label last-axis cross_entropy through the "
            "Pallas fused logsumexp+gather kernel on TPU")
define_flag("use_pallas_paged_attention", True,
            "route paged KV-cache decode attention through the TPU "
            "Pallas kernel (jnp reference elsewhere)")
define_flag("use_pallas_ragged_attention", True,
            "route the serving engine's mixed prefill/decode batches "
            "through the one-launch ragged paged attention Pallas "
            "kernel (per-sequence lengths + page tables as scalar-"
            "prefetch refs) on TPU; the jnp reference runs elsewhere")
define_flag("use_pallas_layer_norm", True,
            "route last-axis layer_norm with weight+bias through the "
            "Pallas fused kernel on TPU")
define_flag("use_pallas_rms_norm", True,
            "route fused_rms_norm through the Pallas kernel on TPU")
define_flag("pallas_gqa", False,
            "allow the Pallas flash BACKWARD for GQA (n_rep>1) on real "
            "TPU; default off — the GQA dkv Mosaic compile has never "
            "finished on record (ROADMAP S3 runs it); interpret-mode "
            "tests cover it regardless")
define_flag("sot_relax_guards", False,
            "SOT-lite: allow widening value-equality guards to shape-only"
            " when a re-record demonstrates an identical op stream and "
            "outputs.  UNSOUND if a host-read value steers python "
            "control flow near a threshold the demonstrations did not "
            "cross — enable only when host reads are logging-only")
define_flag("pp_allow_axis_fallback", False,
            "allow an EXPLICIT pipeline schedule_mode to fall back to "
            "pure-pp host scheduling when mp/sharding/sep/cp axes are "
            "live (default: raise — the requested schedule would "
            "silently not run; the compiled shard_map ring composes "
            "those axes)")
define_flag("while_capture_max_iters", 100000,
            "fuel cap for CONSTRUCTION-TIME evaluation of a captured "
            "static.nn.while_loop (placeholder values may never satisfy "
            "the exit condition); the recorded program always runs the "
            "true unbounded lax.while_loop")
define_flag("sot_error_on_fallback", False,
            "SOT-lite: raise instead of silently running eager when a "
            "signature stops compiling (specialization cap, oversized "
            "guard, RNG during recording).  Use to make every silent "
            "de-optimization loud in perf-critical runs; "
            "paddle.jit.sot.stats() shows the same information passively")
define_flag("pallas_interpret", False,
            "run Pallas kernels in interpreter mode (CPU tests)")
define_flag("pallas_autotune", False,
            "time flash-attention block-size candidates on first use per "
            "(seq, head_dim, dtype) instead of the static heuristic")
define_flag("use_pallas_adamw", True,
            "route the AdamW update through the fused Pallas kernel on TPU")
define_flag("use_pallas_rope", True,
            "route rotary embedding through the fused Pallas kernel on TPU")
define_flag("use_pallas_fused_decode", True,
            "route the compiled decode loop's per-token body through the "
            "fused Pallas decode kernels (rope+QKV, attention+cache-"
            "append, norm+MLP) on TPU; the jnp reference composition "
            "runs elsewhere")
define_flag("megakernel_decode", False,
            "generate() runs the whole token loop as ONE jitted "
            "lax.while_loop program (models/generation.decode_loop): "
            "preallocated token buffer, donated KV-cache carries, "
            "on-device sampling + EOS tracking — zero host transfers "
            "per token.  Beam search, paged caches and models without "
            "a decode-step builder fall back to the eager loop "
            "(observable via the decode_loop event)")
define_flag("serving_engine", False,
            "route InferenceServer POST /generate through the "
            "continuous-batching ServingEngine (paddle_tpu.serving): "
            "iteration-level admission, ragged paged attention, prefix-"
            "cache sharing, per-request token streaming.  Off: the "
            "endpoint answers 404 and only the npz /predict path "
            "serves")
define_flag("serving_fused_steps", 1,
            "serving engine: fuse up to N ragged batch iterations into "
            "ONE jitted lax.while_loop dispatch (the persistent-program "
            "serving step).  The compiled window keeps EOS/budget "
            "tracking, page-append cursors and sampling keys on device "
            "and exits early when a sequence finishes or page pressure "
            "binds; the host sees one packed read per window.  1 (the "
            "default) keeps the classic one-dispatch-per-step path; "
            "prefill and eviction-pressured steps always run the "
            "single-step path regardless")
define_flag("eager_finished_sync_every", 8,
            "eager decode loop: poll finished.all() on the host only "
            "every K generated tokens (the exact eager stop point is "
            "reconstructed from the token buffer, so outputs are "
            "unchanged); 1 restores the per-token sync")
def _apply_transfer_guard(val: str):
    """Race-detection aid (SURVEY.md §5): surface implicit host<->device
    transfers — the TPU analogue of the reference's stream-safety
    debugging flags.  Values: allow | log | disallow."""
    if val not in ("allow", "log", "disallow", "log_explicit",
                   "disallow_explicit"):
        raise ValueError(
            f"FLAGS_transfer_guard must be allow/log/disallow, got {val!r}")
    import jax
    jax.config.update("jax_transfer_guard", val)


define_flag("transfer_guard", "allow",
            "guard implicit host<->device transfers (allow|log|disallow)",
            on_change=_apply_transfer_guard)


define_flag("tuning_cache_dir", "",
            "directory for the persistent autotune/plan caches "
            "(flash_blocks + engine_plan JSONL stores, perf_model.json); "
            "empty: disabled.  The XLA compilation cache is not placed "
            "here: see paddle_tpu/__init__.py")


def _apply_fault_schedule(text: str):
    """Deterministic chaos layer (paddle_tpu.resilience.faults): parse
    and install the fault-injection schedule.  A malformed schedule
    raises here, so set_flags rejects it and an env typo warns at
    import instead of silently not injecting."""
    from .resilience.faults import install_schedule
    install_schedule(text)


define_flag("fault_schedule", "",
            "deterministic fault-injection schedule "
            "'point@N=kind[:arg];...' over the named fault points "
            "(step, ckpt_write, collective, compile, serving_step); "
            "kinds: crash, exit, stall, exc, truncate, corrupt, nan "
            "(nan: serving_step only — on-device NaN-logits poison). "
            "Empty: disabled.  See paddle_tpu.resilience.faults",
            on_change=_apply_fault_schedule)
# read lazily by distributed.communication.sanitizer.get_sanitizer()
# on each collective entry — deliberately no on_change hook (the
# sanitizer imports observability for mismatch events, which must not
# load during flag bootstrap)
define_flag("collective_sanitizer", False,
            "cross-check order/shape/dtype/reduce-op fingerprints of "
            "every collective across the mesh before executing; on "
            "mismatch raise CollectiveMismatchError with both ranks' "
            "fingerprint streams (instead of the silent hang) and "
            "emit a collective_mismatch event. "
            "See paddle_tpu.distributed.communication.sanitizer")
# read lazily by observability.lockwatch.make_lock/make_rlock/
# make_condition at construction time — deliberately no on_change hook
# (lockwatch imports observability for contention events, which must
# not load during flag bootstrap).  Set it BEFORE building the engine/
# router/supervisor: already-constructed objects keep their stdlib
# locks.
define_flag("lock_sanitizer", False,
            "instrument the serving tier's Lock/RLock/Condition "
            "objects: record per-thread held-lock sets, detect "
            "lock-order (wait-for) cycles at acquire time and raise "
            "LockOrderError naming both threads' hold stacks instead "
            "of deadlocking; emit lock_contention events past "
            "hold/wait thresholds and export paddle_lock_* metrics. "
            "See paddle_tpu.observability.lockwatch")
def _apply_observability_dir(path: str):
    """One flag, every telemetry stream (paddle_tpu.observability):
    the JSONL event log (step/compile/checkpoint/fault/restart/tuning/
    dispatch records) lands under ``path``; empty disables it and every
    emit site degrades to a single is-None check.  The metrics registry
    is always live — this flag only gates the on-disk event stream."""
    from .observability import events
    events.configure(path or None)


define_flag("observability_dir", "",
            "directory for the structured run-telemetry event log "
            "(events.jsonl; see paddle_tpu.observability and "
            "`python -m paddle_tpu.observability report`); "
            "empty: disabled",
            on_change=_apply_observability_dir)
define_flag("program_passes", "",
            "program-level optimization pass pipeline over captured "
            "static Programs (static/passes) run by Executor/jit before "
            "compilation.  '' disables; '1'/'default' runs the default "
            "pipeline (CSE, constant folding, dead-op elimination, "
            "chain fusion, remat/donation hints); or a comma-separated "
            "explicit pass list (see "
            "paddle_tpu.static.passes.PROGRAM_PASSES).  Every pass is "
            "replay-equivalence verified (analysis.pass_check, PTL601)")
define_flag("pallas_autotune_topk", 4,
            "measured autotune times only the cost model's top-K block "
            "candidates (0: time every valid candidate)")
define_flag("learned_perf_model", True,
            "consult the telemetry-trained performance model "
            "(perf_model.json under FLAGS_tuning_cache_dir; "
            "`python -m paddle_tpu.tuning fit --from-events`) for "
            "flash blocks and Engine plans on shapes never measured — "
            "zero timing runs on a cold cache.  False forces "
            "measurement; no model file falls back to measurement "
            "either way")
define_flag("serving_step_timeout_s", 0.0,
            "serving engine hung-step watchdog (seconds): >0 bounds "
            "every device dispatch (single step or fused window); on "
            "expiry the watchdog dumps the flight recorder, emits a "
            "step_timeout event, abandons the wedged loop thread "
            "(fresh device pools + page pool) and resumes every "
            "running stream via requeue-at-front — token-exact under "
            "deterministic decode, no stream silently truncated.  "
            "0 (default): disabled",
            )
define_flag("serving_predicted_admission", 0.0,
            "per-iteration batch-step cost budget (seconds) for "
            "serving admission: >0 admits new prefills only while the "
            "learned perf model's predicted step cost stays under the "
            "budget (predicted_cost_s rides serving_admit events); "
            "0 or no trained batch_step head: raw page/token caps "
            "only")
define_flag("cudnn_deterministic", False, "map to XLA deterministic ops where possible")
define_flag("embedding_deterministic", 0, "deterministic embedding lookup")
define_flag("log_level", 0, "framework VLOG level")
