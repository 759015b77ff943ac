"""Continuous-batching serving engine — the front-end that joins the
scheduler, the ragged paged attention step and the prefix cache into
one token-streaming service.

One background thread runs the iteration loop: every pass it asks the
scheduler for a :class:`~.scheduler.StepPlan` (admitting / evicting at
token-iteration granularity), executes ONE jitted ragged step for the
whole mixed prefill+decode batch (``models.generation.
build_ragged_decode_step`` + the one-launch ragged paged attention
kernel), samples the next token per sequence ON DEVICE, and reads the
sampled row back in a single host sync at the window boundary — the
only device read in the loop (PTL701).  The loop keeps ONE step ahead
of that read: step N+1 is planned against what step N will commit and
dispatched before N's row is read (a decoding lane's token reaches it
on the device), so the host's commit, plan and dispatch run under the
device's step and not beside it (``_loop_body``).

With ``FLAGS_serving_fused_steps > 1`` the steady-state decode window
widens: up to N ragged iterations run inside ONE jitted
``lax.while_loop`` (``models.generation.build_fused_window_step``, the
persistent-program serving step) with EOS/budget tracking, page-append
cursors and the sampling key in the on-device carry.  The loop exits
early when any sequence finishes, and the host sees ONE packed read
per window; while the device runs, the scheduler pre-stages the next
boundary's admission work against the projected post-window state
(double-buffered plan, committed or discarded on exit).  Prefill
steps, eviction-pressured steps and ``fused_steps == 1`` keep the
classic single-step path byte for byte.

Programs are cached per query-chunk width ``Q`` (bucketed to powers of
two), so steady-state decode (``Q == 1``) is exactly one compiled
program regardless of batch composition, and the page pools ride as
DONATED jit arguments — XLA reuses their buffers in place across
iterations on accelerator backends.  A step's tokens reach its program
as packed rows (``Scheduler.plan_step`` lays them out, ``step.packed``
of ``build_ragged_decode_step`` takes them): the program of width ``Q``
runs ``scheduler.step_rows(Q, max_batch)`` rows — ``Q`` and the lanes,
not ``max_batch * Q`` — and only its attention kernel sees ``[B, Q]``.

Observability: ``serving_admit`` / ``batch_step`` / ``evict`` events
(see docs/observability_events.md), queue-depth + batch-occupancy
gauges, per-request end-to-end and time-to-first-token histograms —
all through the PR 4 metrics registry, which is what ``GET /metrics``
exports when the engine serves behind ``InferenceServer``
(``FLAGS_serving_engine``).  The loop thread's time is cut into seven
sibling ``engine:*`` phases (plan, prepare, dispatch, host_read, commit
and two waits) that show in any live profiler trace and, as seconds, in
``batch_step`` (``_LoopPhases``).  While a test or the analyzer
observes the op-dispatch stream (``core.dispatch.observe_op_stream``)
each step also emits a ``serving_prefill`` marker carrying the REAL
fed-token count, which proves that prefix-cache sharing skips prefill
work, and a ``serving_host_sync`` marker per host read.

Fault containment: co-batching couples failure domains — one poisoned
request or one wedged dispatch would otherwise take down every
in-flight stream.  Four interlocking pieces bound the blast radius:

* **poison quarantine** — a failed ragged step is retried by
  bisection over the batch's request ids (the eviction-resume
  machinery makes re-running a chunk token-exact under greedy
  decode); innocents complete unchanged, the isolated offender fails
  alone with a ``quarantine`` event, and its prompt hash is rejected
  at admission from then on (a step that fails while its program is
  still compiling, or after its pools were donated, is a program
  fault instead: it fails the engine with the compiler's message);
* **hung-step watchdog** — ``FLAGS_serving_step_timeout_s`` bounds
  every device dispatch; on expiry the flight recorder dumps, the
  iteration loop relaunches under a new epoch with fresh device pools
  and every survivor requeued at the FRONT (no stream is silently
  truncated);
* **deadlines + cancellation** — ``deadline_s`` requests are swept
  every iteration and cancelled mid-batch (pages and the slot free
  immediately); predicted-cost admission 503s doomed requests up
  front;
* **health state machine** — ``ok → degraded → quarantining →
  failed`` rides ``health_transition`` events and the
  ``paddle_serving_engine_health`` gauge, so the fleet router drains
  a sick replica before its supervisor must restart it.

Chaos hooks: ``FLAGS_fault_schedule`` ``serving_step@N=exc|stall|nan``
(resilience.faults) makes each path provable — ``nan`` rides an
on-device NaN-logits sentinel (a poisoned lane's sampled token
collapses to -1 inside the jitted program, so detection costs no
extra host read).
"""
from __future__ import annotations

import collections
import hashlib
import itertools
import threading
import time
import warnings
from typing import Optional, Sequence

import numpy as np

__all__ = ["ServingEngine"]

from ..core import dispatch as _dispatch
from ..observability import events as _events
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from ..observability.lockwatch import make_condition, make_lock
from ..resilience import faults as _faults
from .prefix_cache import PrefixCache
from .scheduler import PagePool, Request, Scheduler

_QUEUE_DEPTH = _metrics.gauge(
    "paddle_serving_engine_queue_depth",
    "requests waiting for a batch slot", labels=("engine",))
_OCCUPANCY = _metrics.gauge(
    "paddle_serving_engine_batch_occupancy",
    "sequences in the running batch", labels=("engine",))
_REQ_LATENCY = _metrics.histogram(
    "paddle_serving_engine_request_seconds",
    "end-to-end request wall time (queue + prefill + decode)",
    labels=("engine",), buckets=_metrics.TIME_BUCKETS)
_TTFT = _metrics.histogram(
    "paddle_serving_engine_ttft_seconds",
    "submit-to-first-token wall time",
    labels=("engine",), buckets=_metrics.TIME_BUCKETS)
_STEP_LATENCY = _metrics.histogram(
    "paddle_serving_engine_step_seconds",
    "one ragged batch iteration (dispatch + boundary sync)",
    labels=("engine",), buckets=_metrics.TIME_BUCKETS)
_TOKENS = _metrics.counter(
    "paddle_serving_engine_tokens_total",
    "tokens processed, by phase (prefill: prompt KV built; decode: "
    "generated)", labels=("engine", "phase"))
_EVICTIONS = _metrics.counter(
    "paddle_serving_engine_evictions_total",
    "running sequences preempted for pages", labels=("engine",))
_STEPS = _metrics.counter(
    "paddle_serving_engine_steps_total",
    "ragged batch iterations executed", labels=("engine",))
_DISPATCHES = _metrics.counter(
    "paddle_serving_engine_dispatches_total",
    "jitted program launches (a fused window is ONE dispatch covering "
    "fused_steps iterations)", labels=("engine",))
_HEALTH = _metrics.gauge(
    "paddle_serving_engine_health",
    "engine health state machine (0 ok / 1 degraded / 2 quarantining "
    "/ 3 failed) — the fleet router consumes this to drain sick "
    "replicas before their supervisor must restart them",
    labels=("engine",))
_QUARANTINED = _metrics.counter(
    "paddle_serving_engine_quarantined_total",
    "requests quarantined (poison isolation / NaN-logits sentinel)",
    labels=("engine",))
_CANCELLED = _metrics.counter(
    "paddle_serving_engine_cancelled_total",
    "requests cancelled (deadline, client disconnect, consumer "
    "timeout)", labels=("engine",))
_STEP_TIMEOUTS = _metrics.counter(
    "paddle_serving_engine_step_timeouts_total",
    "hung-step watchdog firings (each one dumps the flight recorder "
    "and relaunches the iteration loop)", labels=("engine",))

# the health ladder the gauge exports; "failed" is terminal for the
# engine object (the fleet supervisor restarts the whole replica)
_HEALTH_RANK = {"ok": 0, "degraded": 1, "quarantining": 2, "failed": 3}

# extra watchdog budget for a dispatch that misses the program cache:
# its wall time is dominated by trace+compile (minutes on a real TPU),
# which must never be mistaken for a hung device.  A stall injected or
# occurring during a cold dispatch is still caught — just this much
# later.
_COLD_DISPATCH_GRACE_S = 120.0

_ENGINE_SEQ = itertools.count(1)


def _mark_op_stream(name: str, n: int) -> None:
    """A marker in the op-dispatch stream (``core.dispatch.
    observe_op_stream``) whose one input has ``n`` elements: the REAL
    count of tokens fed, or of iterations a host read covered.  Costs
    one check when nobody listens."""
    if n and _dispatch._op_stream_hooks:
        _dispatch._emit_op_event(name, [np.empty((n,), "int8")], [], True)


# The loop thread's time, cut into sibling phases.  Each is a
# ``profiler.RecordEvent`` of this name, so it lands on the host plane
# of whatever profiler trace is live (``jax.profiler``, TensorBoard,
# paddle_tpu's own ``Profiler``) on the device plane's clock; the same
# stretches, as seconds, are the ``*_s`` fields of ``batch_step``.
_IDLE_WAIT, _NO_CAPACITY_WAIT, _PLAN, _PREPARE, _DISPATCH, _HOST_READ, \
    _COMMIT = range(7)
_PHASE_NAMES = ("engine:idle_wait", "engine:no_capacity_wait",
                "engine:plan", "engine:prepare", "engine:dispatch",
                "engine:host_read", "engine:commit")
_NO_PHASES = (None,) * 8


class _LoopPhases:
    """One loop thread's phase clock.  ``switch`` closes the running
    phase and opens the next, so the phases tile the thread's time with
    no gap between them and none inside another.

    With the event log off that is all it does: two annotation calls a
    switch, no clock read, nothing allocated.  With it on, ``switch``
    also reads ``perf_counter`` once and sums the closed phase into
    ``seconds`` (indexed by phase).  A step's record takes them in two
    halves, because the loop dispatches a step before it reads the one
    before: ``dispatched`` hands back what it took to get the step out
    (``plan_s``, ``prepare_s``, ``dispatch_s``) and ``take`` joins that
    with what it took to get its result in (``read_s``, ``commit_s``),
    with:

    * ``host_gap_s`` — from the end of the last host read (or of the
      last dispatch call, where no read came since: a second step sent
      behind the first) to the end of this step's dispatch call, the
      waits for work or capacity in between taken out (``wait_s``): the
      host work a step carries.
      With no step in flight the device waits for all of it; with one
      in flight, only for what exceeds that step's device time;
    * ``admit_queue_s`` — the queue wait of each request this step's
      plan admitted.
    """

    __slots__ = ("_spans", "_cur", "_t_cur", "seconds", "_gap_from",
                 "_waited", "_gap", "admit_queue_s", "result_at")

    def __init__(self):
        from ..profiler.profiler import RecordEvent
        self._spans = tuple(RecordEvent(n) for n in _PHASE_NAMES)
        self._cur = -1                  # the running phase
        self._t_cur = None              # its start; None: not timed
        self.seconds = None             # seconds by phase, not yet taken
        self._gap_from = None           # end of the last host read, or
        # of a later dispatch call
        self._waited = 0.0              # waits since then
        self._gap = None                # (host_gap_s, wait_s)
        self.admit_queue_s = None
        # when the last step's result reached the host (time.monotonic;
        # the engine's own, for step_s: kept with the log off too)
        self.result_at = 0.0

    def switch(self, phase: int) -> None:
        cur = self._cur
        if cur >= 0:
            self._spans[cur].end()
        if _events.enabled():
            now = time.perf_counter()  # noqa: PTL501 — the batch_step record's own phase seconds: they reach the event log, and the profiler's clock is no registry histogram
            if self._t_cur is not None:
                self._close(cur, now)
            self._t_cur = now
        elif self._t_cur is not None or self._gap_from is not None:
            self._forget()              # the log was switched off
        self._cur = phase
        if phase >= 0:
            self._spans[phase].begin()

    def stop(self) -> None:
        """Close the running phase (the loop thread is leaving)."""
        self.switch(-1)

    def _close(self, cur: int, now: float) -> None:
        dt = now - self._t_cur
        if cur <= _NO_CAPACITY_WAIT:
            self._waited += dt
            return
        if self.seconds is None:
            self.seconds = [0.0] * len(_PHASE_NAMES)
        self.seconds[cur] += dt
        if cur == _DISPATCH:
            if self._gap_from is not None:
                self._gap = (now - self._gap_from - self._waited,
                             self._waited)
            self._gap_from, self._waited = now, 0.0
        elif cur == _HOST_READ:
            self._gap_from, self._waited = now, 0.0

    def _forget(self) -> None:
        self.drop()
        self._gap_from = self.admit_queue_s = None
        self._waited = 0.0

    def admitted(self, queue_s: float) -> None:
        if self._t_cur is not None:
            if self.admit_queue_s is None:
                self.admit_queue_s = []
            self.admit_queue_s.append(queue_s)

    def drop(self) -> None:
        """A step that failed leaves no record: its seconds go; the
        queue waits of what it admitted ride the next record."""
        self._t_cur = self.seconds = self._gap = None

    def dispatched(self, phase: int):
        """A step's dispatch call returned and the thread goes on to
        ``phase``: ``(plan_s, prepare_s, dispatch_s, host_gap_s, wait_s,
        admit_queue_s)`` of that step, for ``take`` when its result is
        in; None with the event log off."""
        self.switch(phase)
        secs = self.seconds
        if self._t_cur is None or secs is None:
            return None
        gap, queue = self._gap, self.admit_queue_s
        self._gap = self.admit_queue_s = None
        out = [round(secs[i], 6) for i in (_PLAN, _PREPARE, _DISPATCH)]
        secs[_PLAN] = secs[_PREPARE] = secs[_DISPATCH] = 0.0
        if gap is None:
            out += [None, None]
        else:
            out += [round(gap[0], 6), round(gap[1], 6) or None]
        out.append(queue)
        return out

    def take(self, front):
        """``(plan_s, prepare_s, dispatch_s, read_s, commit_s,
        host_gap_s, wait_s, admit_queue_s)`` of the step that is about
        to be recorded: ``front`` is what ``dispatched`` gave at its
        dispatch, the read and the commit are the thread's since, the
        running phase counted up to now; all None with the event log
        off.  What the thread does from here to the next ``switch`` (the
        record's own write) stays under the running annotation and
        inside the next ``host_gap_s``, and is summed into no phase."""
        if self._t_cur is None or front is None:
            return _NO_PHASES
        self._close(self._cur, time.perf_counter())  # noqa: PTL501 — as in switch()
        self._t_cur = None
        secs = self.seconds
        back = [round(secs[_HOST_READ], 6), round(secs[_COMMIT], 6)]
        secs[_HOST_READ] = secs[_COMMIT] = 0.0
        return front[:3] + back + front[3:]


class _Flight:
    """One step that was dispatched and whose sampled tokens the host
    has not read: what the loop needs to read it, record it, and undo
    it if its result never comes."""

    __slots__ = ("plan", "ahead", "cold", "pools_in", "key_in", "span",
                 "bracket_t0", "call_at", "nxt", "front")

    def __init__(self, plan, ahead: bool):
        self.plan = plan
        self.ahead = ahead      # dispatched while the step before it
                                # was unread
        self.cold = False       # its program compiled in its dispatch
        self.pools_in = self.key_in = None  # what its program consumed
        self.span = _tracing.NOOP_SPAN      # its batch_step trace span
        self.bracket_t0 = self.call_at = 0.0    # time.monotonic: its
        # preparation's start (the watchdog's bracket) and its
        # program's call
        self.nxt = None         # its sampled row, on the device
        self.front = None       # _LoopPhases.dispatched()


class ServingEngine:
    """Continuous-batching LLM serving over one model.

    ``submit()`` returns a :class:`~.scheduler.Request` whose
    ``stream()`` yields generated token ids live and whose ``wait()``
    blocks for the full result.  Greedy by default; a per-request
    ``temperature > 0`` samples on device from the engine's PRNG
    stream.  Use as a context manager or call ``start()``/``stop()``.
    """

    def __init__(self, model, *, max_batch: int = 8, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_pages_per_seq: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 max_queue: int = 1024, max_prefill_chunk: int = 0,
                 prefix_caching: bool = True, seed: int = 0,
                 dtype: str = "float32", perf_model="auto",
                 max_step_cost_s: Optional[float] = None,
                 health_recovery_steps: int = 64,
                 max_watchdog_relaunches: int = 3):
        import jax
        from ..flags import get_flag
        if hasattr(model, "eval"):
            model.eval()
        self.model = model
        self._params, self._step_fn = model.build_ragged_decode_step()
        # the pools' geometry is the step's to say, layer by layer
        # (models.generation.CacheDescription)
        self._cache = self._step_fn.cache
        cfg = model.config
        ps = int(page_size)
        max_pos = int(getattr(cfg, "max_position_embeddings", 1024))
        if max_pages_per_seq is None:
            max_pages_per_seq = -(-max_pos // ps)
        if num_pages is None:
            # every slot can hold a max-length sequence, plus the sink
            num_pages = int(max_batch) * int(max_pages_per_seq) + 1
        # window layers keep a ring a lane, sized for the widest chunk
        # a step may write before it attends
        self._ring_pages = self._cache.ring_pages(
            ps, int(max_prefill_chunk) or max_pos)
        # layers that keep something a running sequence owns alone, a
        # ring or a state: the sequence then holds one of max_batch slots
        self._n_state = self._cache.n_state
        # the heads of each linear-attention layer: whether a chunk of
        # more than one row takes the chunked form of such a layer (a
        # short-convolution layer, the other kind of state layer, has
        # none), and whether that form is the kernel
        # (_scan_kernel_layers)
        self._linear_heads = tuple(
            d.linear_attention.heads for d in cfg.description().layers
            if d.linear_attention is not None)
        slotted = bool(self._ring_pages or self._n_state)
        if slotted and prefix_caching:
            raise ValueError(
                f"{type(model).__name__} has layers whose cache is a "
                f"running sequence's own ({self._cache.n_window} window "
                f"attention layers, {self._n_state} state layers) and "
                f"holds no earlier position: a ring keeps the last "
                f"{self._cache.window} tokens and a chunk, a state the "
                f"whole prefix folded into one array.  PrefixCache shares "
                f"PagePool pages and cannot restore a ring's tail or a "
                f"lane's state at the matched length yet — pass "
                f"prefix_caching=False")
        # the fused window (generation.build_fused_window_step) takes
        # one append cursor a lane from tables and carries no routing
        # counts, and reads its pools as key-value pairs: a model with a
        # ring, a state, a latent or an expert layer keeps the
        # single-step path, and asking for more is refused here, to the
        # caller, not later inside the serving loop
        self._routed = self._step_fn.routing_counts
        self._fusable = not slotted and not self._routed \
            and not self._cache.n_latent
        if not self._fusable and int(get_flag("serving_fused_steps")
                                     or 1) > 1:
            raise ValueError(
                f"FLAGS_serving_fused_steps="
                f"{get_flag('serving_fused_steps')} with "
                f"{type(model).__name__}: the fused window takes no model "
                f"with window attention, state, latent or expert layers "
                f"— set it to 1")
        self.pool = PagePool(num_pages, ps)
        self.prefix_cache = PrefixCache(self.pool) if prefix_caching \
            else None
        # device-pool geometry, kept so a watchdog relaunch can build
        # FRESH buffers (the wedged dispatch may still write into the
        # old ones — they are abandoned wholesale, never reused)
        self._num_pages, self._page_size = int(num_pages), ps
        self._dtype = dtype
        # the attention launches of a step, one entry a geometry:
        # ((key width, window or None, query heads), layers) — what
        # _attn_blocks and _attn_tiles count
        md = cfg.description()
        self._attn_launches = tuple(collections.Counter(
            (layer[1], d.attention.window, d.attention.heads or md.heads)
            for layer, d in zip(self._cache.layers, md.layers)
            if d.attention is not None).items())
        # keys a row of a latent layer's index keeps (0: no such layer,
        # and a row attends what it sees) — what _select_counts counts by
        self._index_topk = next(
            (d.latent_attention.index.top_k
             for d in cfg.description().layers
             if d.latent_attention is not None
             and d.latent_attention.index is not None), 0) \
            if self._cache.n_latent else 0
        # the expert layers of a step, one entry a number of picks a
        # row: (top_k, layers) — what _expert_kernel_layers counts by
        self._expert_layers = tuple(collections.Counter(
            d.feed_forward.top_k for d in cfg.description().layers
            if d.feed_forward.held is not None).items()) \
            if self._routed else ()
        self._itemsize = jax.numpy.dtype(dtype).itemsize
        self._prefix_caching = bool(prefix_caching)
        # predicted-cost admission (FLAGS_serving_predicted_admission,
        # seconds): the scheduler admits prefills against the learned
        # model's predicted batch-step cost instead of raw caps alone.
        # perf_model="auto" loads the trained model from
        # FLAGS_tuning_cache_dir; pass a model object to inject one, or
        # None to disable regardless of the flag.
        if max_step_cost_s is None:
            max_step_cost_s = float(
                get_flag("serving_predicted_admission") or 0.0)
        if perf_model == "auto":
            perf_model = None
            if max_step_cost_s > 0:
                from ..tuning import learned as _learned
                perf_model = _learned.load_model()
        if perf_model is not None and not perf_model.has("batch_step"):
            perf_model = None
        self.scheduler = Scheduler(
            self.pool, max_batch, max_pages_per_seq,
            prefix_cache=self.prefix_cache, max_queue=max_queue,
            max_prefill_chunk=max_prefill_chunk,
            max_seq_len=max_pos, perf_model=perf_model,
            max_step_cost_s=max_step_cost_s,
            ring_pages=self._ring_pages,
            lane_tables=self._cache.tables if slotted else None)
        self.max_batch = int(max_batch)
        self.default_eos = None if eos_token_id is None \
            else int(eos_token_id)
        self._pools = self._new_pools()
        self._key = jax.random.PRNGKey(int(seed))
        # what a step's program takes as ``prev`` when no step before
        # it is unread: the shape of a step's sampled row (the routing
        # counts behind it where a model routes), on the device once
        self._no_prev = jax.numpy.zeros(
            (int(max_batch) + (3 if self._routed else 0),), "int32")
        self._programs: dict = {}
        self.engine_id = str(next(_ENGINE_SEQ))
        eid = self.engine_id
        self._g_queue = _QUEUE_DEPTH.labels(engine=eid)
        self._g_occ = _OCCUPANCY.labels(engine=eid)
        self._h_latency = _REQ_LATENCY.labels(engine=eid)
        self._h_ttft = _TTFT.labels(engine=eid)
        self._h_step = _STEP_LATENCY.labels(engine=eid)
        self._c_prefill = _TOKENS.labels(engine=eid, phase="prefill")
        self._c_decode = _TOKENS.labels(engine=eid, phase="decode")
        self._c_evict = _EVICTIONS.labels(engine=eid)
        self._c_steps = _STEPS.labels(engine=eid)
        self._c_dispatch = _DISPATCHES.labels(engine=eid)
        self._g_health = _HEALTH.labels(engine=eid)
        self._g_health.set(0)
        self._c_quarantined = _QUARANTINED.labels(engine=eid)
        self._c_cancelled = _CANCELLED.labels(engine=eid)
        self._c_step_timeout = _STEP_TIMEOUTS.labels(engine=eid)
        self._lock = make_lock("serving.engine._lock")
        self._wake = make_condition("serving.engine._wake", self._lock)
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self._accepting = False
        # -- fault containment state (all under self._lock) --
        # epoch fences the loop thread and in-flight dispatches: a
        # watchdog relaunch bumps it, and any zombie thread that wakes
        # later sees the mismatch and drops its result on the floor
        self._epoch = 0
        self._dispatch_t0: Optional[float] = None
        self._dispatch_plan = None
        # a dispatch that misses the program cache spends its time in
        # trace+compile, not device execution — the watchdog grants it
        # _COLD_DISPATCH_GRACE_S on top of the step budget so a slow
        # compile (routine after a relaunch re-prefills into a new
        # Q-bucket) is never mistaken for a hung device
        self._dispatch_cold = False
        self._step_timeout_s = 0.0
        self._watchdog: Optional[threading.Thread] = None
        self._relaunches = 0
        self.max_watchdog_relaunches = int(max_watchdog_relaunches)
        self.health = "ok"
        self._clean_steps = 0
        self.health_recovery_steps = int(health_recovery_steps)
        # prompt_hash -> offence count: repeat offenders rejected at
        # admission (the poison travels with the prompt, not the id)
        self._quarantined: dict = {}
        # request id -> (kind, arg): chaos-injected sticky poison
        # pinned to a request so quarantine bisection is deterministic
        self._poison: dict = {}
        self._n_quarantined = 0
        self._n_cancelled = 0
        self._wedged_threads = 0
        # steps dispatched while the step before them was unread, and
        # steps dispatched with nothing unread (a fused window is one)
        self._n_ahead = 0
        self._n_drained = 0
        # what the steps asked of the state layers (_state_counts)
        self._state_lanes = self._state_resets = self._scan_rows = 0
        # key blocks the steps' attention kernels walked (_attn_blocks),
        # their live query tiles and their grid steps (_attn_tiles)
        self._n_attn_blocks = 0
        self._n_attn_tiles = 0
        self._n_attn_tile_slots = 0
        # keys the steps' rows attended in full and in window layers
        # (_keys_attended)
        self._attn_keys_full = self._attn_keys_window = 0
        # expert layers the steps ran as the kernel that reads a picked
        # expert's weights itself (_expert_kernel_layers)
        self._n_expert_kernel_layers = 0
        # linear-attention layers the steps ran as the chunked scan's
        # kernel (_scan_kernel_layers)
        self._n_scan_kernel_layers = 0
        # what the steps' rows asked of a latent layer's index
        # (_select_counts)
        self._select_rows = self._keys_visible = self._keys_selected = 0

    def _new_pools(self):
        """Zeroed device pools and states of the step's own geometry."""
        return self._cache.new_pools(
            self._num_pages, self._page_size, self._dtype, self.max_batch,
            self._ring_pages)

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ServingEngine":
        from ..flags import get_flag
        with self._wake:
            if self._running:
                return self
            self._running = True
            self._accepting = True
            timeout_s = float(
                get_flag("serving_step_timeout_s") or 0.0)
            self._step_timeout_s = timeout_s
            epoch = self._epoch
        # single creator: the _running CAS above guarantees exactly one
        # start() reaches here, and stop() must join without the lock
        self._thread = threading.Thread(target=self._loop, args=(epoch,),  # noqa: PTL902 — sole-winner write; joiners read the handle lock-free by design
                                        daemon=True,
                                        name=f"serving-engine-"
                                             f"{self.engine_id}")
        self._thread.start()
        if timeout_s > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop,
                args=(timeout_s,), daemon=True,
                name=f"serving-watchdog-{self.engine_id}")
            self._watchdog.start()
        return self

    def stop(self, drain: bool = True, timeout: float = 30.0,
             join_timeout: float = 5.0) -> dict:
        """Stop accepting requests; with ``drain`` finish every
        admitted/queued request first (bounded by ``timeout``), else
        fail them fast.  Returns ``{"engine", "health", "wedged"}`` —
        ``wedged=True`` means the loop thread failed to join within
        ``join_timeout`` (a hung device dispatch survived shutdown);
        the flight recorder is dumped and health goes ``failed`` so
        the leak is loud instead of silent."""
        with self._wake:
            self._accepting = False
            self._wake.notify_all()
        if drain:
            deadline = time.monotonic() + float(timeout)
            while time.monotonic() < deadline:
                with self._lock:
                    if not self.scheduler.has_work():
                        break
                time.sleep(0.01)
        with self._wake:
            self._running = False
            # fail whatever is left (drain timeout, or drain=False)
            leftovers = list(self.scheduler.waiting) \
                + list(self.scheduler.running)
            self.scheduler.waiting.clear()
            for seq in leftovers:
                self.scheduler.finish(seq, error="engine stopped")
            self._wake.notify_all()
        wedged = False
        t = self._thread
        if t is not None:
            t.join(timeout=join_timeout)
            if t.is_alive():
                wedged = True
                self._wedged_threads += 1
                warnings.warn(
                    f"serving engine {self.engine_id}: loop thread "
                    f"failed to join within {join_timeout}s — a wedged "
                    f"device dispatch is leaking a thread",
                    stacklevel=2)
                _tracing.dump_flight("serving_stop_wedged")
                with self._lock:
                    self._set_health("failed",
                                     "loop thread wedged at stop")
            self._thread = None
        wd = self._watchdog
        if wd is not None:
            wd.join(timeout=max(float(join_timeout), 1.0))
            self._watchdog = None
        return {"engine": self.engine_id, "health": self.health,  # noqa: PTL902 — post-join snapshot: both threads are dead by here
                "wedged": wedged}

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- request side ----------------------------------------------------
    def submit(self, input_ids, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0,
               request_id: Optional[str] = None,
               trace=None,
               deadline_s: Optional[float] = None) -> Request:
        """Queue one generation request; returns the live handle.
        ``trace`` is an optional :class:`~..observability.tracing.
        TraceContext` to parent the request's root span on (the HTTP
        layer passes the client ``traceparent`` here); without it a
        fresh trace roots at this request when tracing is enabled.
        ``deadline_s`` bounds the request end to end: it is 503'd up
        front when predicted cost says it cannot finish in time, and
        cancelled mid-batch (pages freed immediately) when the
        deadline passes while it runs."""
        req = Request(input_ids, max_new_tokens=max_new_tokens,
                      eos_token_id=(self.default_eos if eos_token_id
                                    is None else eos_token_id),
                      temperature=temperature, request_id=request_id,
                      deadline_s=deadline_s)
        req._cancel_cb = self._cancel_request
        root = _tracing.start_span(
            "serving_request", parent=trace,
            attrs={"request": req.id, "engine": self.engine_id,
                   "prompt_len": len(req.prompt),
                   "max_new_tokens": req.max_new_tokens})
        if root is not _tracing.NOOP_SPAN:
            req.trace = root.context
            req._root_span = root
            req._queue_span = _tracing.start_span("queue", parent=root)
        with self._wake:
            if not self._accepting:
                if self.health == "failed":
                    req.error_kind = "unhealthy"
                    req._finish(error="engine is unhealthy (failed)")
                else:
                    req._finish(error="engine is not accepting "
                                      "requests")
                return req
            h = self._prompt_hash(req.prompt)
            if h in self._quarantined:
                # repeat offender: this exact prompt already poisoned
                # a batch — reject at admission instead of letting it
                # fail another co-scheduled batch
                req.error_kind = "quarantined"
                _events.emit("quarantine", request=req.id,
                             reason="repeat offender (prompt hash "
                                    "previously quarantined)",
                             prompt_hash=h, action="rejected", batch=0)
                req._finish(error=f"prompt quarantined after "
                                  f"{self._quarantined[h]} prior "
                                  f"failure(s) (hash {h})")
                return req
            self.scheduler.submit(req)
            self._g_queue.set(self.scheduler.queue_depth())
            self._wake.notify()
        return req

    def generate(self, input_ids, timeout: Optional[float] = 600.0, **kw):
        """Synchronous convenience: submit + wait.  The wait covers a
        request whose programs are still to compile (a width's first
        request compiles the prefill program and the decode-only one:
        over a minute together, cold, for a model of eight layers with
        six scans each, where ``Request.wait``'s own 60 s cancelled the
        request and failed the caller)."""
        return self.submit(input_ids, **kw).wait(timeout)

    # -- the iteration loop ----------------------------------------------
    def _loop(self, epoch: int):
        """One engine epoch of the iteration loop.  The watchdog bumps
        ``self._epoch`` and launches a replacement thread when a
        dispatch hangs; this wrapper also catches a loop-level crash
        (a planning bug, not a step failure — those are contained
        per-step) so the engine fails LOUDLY instead of leaving every
        consumer blocked on a dead thread."""
        phases = _LoopPhases()
        try:
            self._loop_body(epoch, phases)
        except Exception as e:  # noqa: BLE001 — last-resort
            # containment: the loop thread dying silently would hang
            # every consumer; report + fail everything + mark failed
            warnings.warn(f"serving engine loop died: "
                          f"{type(e).__name__}: {e}", stacklevel=1)
            with self._wake:
                if epoch != self._epoch:
                    return          # a relaunch already superseded us
                self._accepting = False
                self._set_health("failed", f"loop thread died: "
                                           f"{type(e).__name__}")
                self._fail_all_locked(f"engine loop failed: "
                                      f"{type(e).__name__}: {e}")
        finally:
            phases.stop()

    def _may_run_ahead(self) -> bool:
        """Whether the loop may plan and dispatch the next step while
        the last one's tokens are still on the device (under ``_wake``).
        It may not while a failure is being contained or provoked — a
        bisection episode probes one plan at a time, a pinned poison
        fails every plan that holds its request, and a replica that is
        not ``ok`` is being watched step by step — nor where decode-only
        plans go to the fused window, which reads its first tokens on
        the host.  The loop then reads and commits the unread step
        first and plans as a loop that never ran ahead would."""
        return self.health == "ok" and not self.scheduler.bisect_groups \
            and not self._poison and self._fused_max() <= 1

    def _fused_max(self) -> int:
        """``FLAGS_serving_fused_steps`` where the model's step can run
        as a fused window, else 1."""
        from ..flags import get_flag
        return int(get_flag("serving_fused_steps") or 1) \
            if self._fusable else 1

    def _loop_body(self, epoch: int, phases: _LoopPhases):
        """The loop keeps ONE step unread behind the step it dispatches:

            dispatch(N) . plan(N+1) . dispatch(N+1) . read(N) . commit(N)
                        . plan(N+2) . dispatch(N+2) . read(N+1) . ...

        so that when the device ends step N, step N+1 is queued behind
        it and the host's commit, record, plan and dispatch run under
        the device's step.  Step N+1 is planned before N's tokens are
        on the host (``Scheduler.plan_step(unread)``) and takes them on
        the device (``prev`` / ``take`` of ``_program``).  Where that is
        unsafe or pointless (``_may_run_ahead``, a plan that would have
        to evict, nothing runnable) the loop lands the unread step
        first — reads it, commits it — and plans again."""
        flight: Optional[_Flight] = None    # dispatched, not read yet
        while True:
            # the wait for _wake counts as planning: it is where the
            # client threads contend with this one
            phases.switch(_PLAN)
            plan = None
            fused_w, fused_max, fused_reason = 1, 0, "single_step"
            with self._wake:
                if not self._running or epoch != self._epoch:
                    if flight is not None:
                        # stopped or superseded: whoever finished the
                        # requests left nothing for this result to reach
                        flight.span.end(status="cancelled")
                    return
                self._sweep_deadlines_locked()
                if flight is not None and not self._may_run_ahead():
                    pass                # land it, then plan
                elif self.scheduler.has_work():
                    plan, admitted, evicted = self.scheduler.plan_step(
                        flight.plan if flight is not None else None)
                    self._note_plan_locked(admitted, evicted, phases)
                elif flight is None:
                    phases.switch(_IDLE_WAIT)
                    self._wake.wait(0.05)
                    continue
                # fused-window eligibility: pure steady-state decode
                # only (no prefill chunk, Q == 1).  window_budget then
                # clamps N to what the pool can host WITHOUT eviction
                # and pre-allocates the window's pages; W == 1 keeps
                # the single-step path — including all of its eviction
                # machinery — byte for byte
                if plan is not None and plan.n_prefill == 0 \
                        and plan.q_width == 1 \
                        and not self.scheduler.bisect_groups:
                    # (a bisection episode pins the single-step path:
                    # probe batches must fail one iteration at a time;
                    # with the flag above 1 no step is ever unread here)
                    fused_max = self._fused_max()
                    if fused_max > 1:
                        fused_w, fused_reason = \
                            self.scheduler.window_budget(plan,
                                                         fused_max)
            if plan is None and flight is None:
                # runnable work exists but no pages/slots right now
                # (e.g. the queue head cannot fit until a decode
                # finishes) — yield briefly instead of spinning
                phases.switch(_NO_CAPACITY_WAIT)
                time.sleep(0.005)
                continue
            landing: Optional[_Flight] = None
            nxt: Optional[_Flight] = None
            try:
                if fused_w > 1:
                    phases.switch(_PREPARE)
                    if self._open_bracket(plan, epoch) is None:
                        return
                    self._run_window(plan, fused_w, fused_max,
                                     fused_reason, epoch, phases)
                    self._close_bracket(epoch)
                    continue
                if plan is not None:
                    phases.switch(_PREPARE)
                    nxt = _Flight(plan, ahead=flight is not None)
                    if not self._dispatch_step(nxt, flight, epoch, phases):
                        return          # a relaunch superseded us
                if flight is not None:
                    landing, flight = flight, None
                    self._land_step(landing, nxt, epoch, phases)
                    landing = None
                flight = nxt
            except Exception as e:  # noqa: BLE001 — containment, not
                # crash-out: the batch is retried by bisection and
                # only the isolated offender fails.
                # Taken as a dispatch that failed until shown otherwise:
                # ``cold`` says whether its program was still compiling
                # (the bracket's flag may be an unread step's)
                failed = plan
                cold = nxt.cold if nxt is not None else self._dispatch_cold  # noqa: PTL902 — loop thread is the sole writer
                if landing is None and flight is not None:
                    # the dispatch BEHIND an unread step failed.  That
                    # step is sound: land it, then contain this one
                    landing, flight = flight, None
                    try:
                        self._land_step(landing, None, epoch, phases)
                        landing = None
                    except Exception as e2:  # noqa: BLE001, PTL401 — not swallowed: it takes the first failure's place below
                        e = e2
                if landing is not None:
                    # a READ failed: the step behind it, if any, took
                    # its pools and tokens and goes with it.  Nothing of
                    # either was committed, so the engine's device state
                    # goes back to what the failed step was given
                    failed, cold = landing.plan, landing.cold
                    landing.span.end(status="error")
                    if nxt is not None:
                        nxt.span.end(status="error")
                    with self._lock:
                        if epoch != self._epoch:
                            return
                        self._pools, self._key = \
                            landing.pools_in, landing.key_in
                flight = None
                phases.drop()
                self._close_bracket(epoch)
                if cold or self._pools[0][0].is_deleted():  # noqa: PTL902 — loop thread is the sole writer of both
                    # a PROGRAM fault, not a poisoned request: the
                    # dispatch was still tracing/compiling (a Mosaic or
                    # VMEM refusal would repeat for every request that
                    # reaches this Q bucket), or the failure came after
                    # the pools were donated and there is nothing left
                    # to retry against.  _loop fails the engine loudly
                    # with the compiler's message on every request
                    raise e
                warnings.warn(f"serving step failed: "
                              f"{type(e).__name__}: {e}", stacklevel=1)
                self._contain_step_failure(failed, e, epoch)

    def _note_plan_locked(self, admitted, evicted,
                          phases: _LoopPhases) -> None:
        """The events, spans and gauges of what a plan admitted and
        evicted."""
        now = time.monotonic()
        for seq in admitted:
            req = seq.req
            queue_s = round(now - req.submitted_at, 6)
            phases.admitted(queue_s)
            qs, req._queue_span = req._queue_span, None
            if qs is not None:
                # queue-wait over: prefix-cache hit + resume
                # facts land on the closing span
                qs.end(cached_tokens=seq.cached_tokens,
                       resumed=req.evictions > 0)
            tr = req.trace
            _events.emit(
                "serving_admit", request=req.id,
                prompt_len=len(req.prompt),
                cached_tokens=seq.cached_tokens,
                queue_s=queue_s,
                resumed=req.evictions > 0,
                predicted_cost_s=(
                    round(seq.predicted_cost_s, 6)
                    if seq.predicted_cost_s is not None
                    else None),
                trace_id=tr.trace_id if tr else None,
                span=tr.span_id if tr else None)
        for seq in evicted:
            self._c_evict.inc()
            req = seq.req
            tr = req.trace
            _events.emit(
                "evict", request=req.id,
                kv_len=len(seq.tokens),
                n_generated=seq.n_generated,
                reason="page_exhaustion",
                trace_id=tr.trace_id if tr else None,
                span=tr.span_id if tr else None)
            if tr is not None and req._queue_span is None:
                # requeued: a fresh queue-wait span opens under
                # the same root until re-admission
                req._queue_span = _tracing.start_span(
                    "queue", parent=tr,
                    attrs={"resumed": True})
        self._g_queue.set(self.scheduler.queue_depth())
        self._g_occ.set(len(self.scheduler.running))

    def _open_bracket(self, plan, epoch: int) -> Optional[float]:
        """Watchdog bracket: the dispatch about to start is bounded by
        FLAGS_serving_step_timeout_s from here.  With a step unread the
        bracket is already open and stays that (older) step's.  Returns
        the time (``time.monotonic``) from which this dispatch would
        hold it; None: a relaunch superseded this thread."""
        now = time.monotonic()
        with self._lock:
            if epoch != self._epoch:
                return None
            if self._dispatch_t0 is None:
                self._dispatch_t0 = now
                self._dispatch_plan = plan
                self._dispatch_cold = False
        return now

    def _pass_bracket_locked(self, behind: Optional[_Flight]) -> None:
        """A step's result is in (or it failed): the bracket passes to
        the step still unread, from that step's own dispatch on, or
        closes."""
        if behind is None:
            self._dispatch_t0 = self._dispatch_plan = None
        else:
            self._dispatch_t0 = behind.bracket_t0
            self._dispatch_plan = behind.plan
        # (whatever is still unread has been compiled: its call returned)
        self._dispatch_cold = False

    def _close_bracket(self, epoch: int) -> None:
        with self._lock:
            if epoch == self._epoch:
                self._pass_bracket_locked(None)

    def _maybe_poison(self, plan):
        """Chaos hook (``serving_step@N=exc|nan``): a fired fault pins
        STICKY poison to the first request of the triggering batch, so
        every retry containing it fails deterministically and the
        quarantine bisection provably converges on it.  Returns the
        lane index to NaN-poison on device, or None."""
        _faults.maybe_fault("serving_step")
        directive = _faults.take_serving_poison()
        if directive is not None and plan.seqs:
            self._poison[plan.seqs[0].req.id] = directive
        lane = None
        for i, seq in enumerate(plan.seqs):
            d = self._poison.get(seq.req.id)
            if d is None:
                continue
            if d[0] == "exc":
                raise _faults.InjectedFault(
                    f"injected serving_step poison "
                    f"(request {seq.req.id})")
            lane = i                      # kind "nan": poison on device
        return lane

    def _dispatch_step(self, step: _Flight, unread: Optional[_Flight],
                       epoch: int, phases: _LoopPhases) -> bool:
        """Hand ``step.plan`` to the device and return without reading
        its result.  ``unread`` is the step dispatched before it whose
        tokens are still on the device (the plan was made against it):
        its ``nxt`` is this program's ``prev``.  False: a watchdog
        relaunch superseded this thread and the result is nobody's."""
        plan = step.plan
        # snapshot the device state FIRST: if this thread stalls and
        # the watchdog relaunches around it, the zombie must keep
        # writing into the ABANDONED buffers it captured here — never
        # into the fresh epoch's pools (self._pools by then)
        pools_in, key_in = self._pools, self._key  # noqa: PTL902 — THE zombie-containment snapshot: lock-free on purpose, see comment above
        step.pools_in, step.key_in = pools_in, key_in
        step.bracket_t0 = self._open_bracket(plan, epoch)
        if step.bracket_t0 is None:
            return False
        # one SHARED step span for the whole ragged iteration, linked
        # from every member request's trace — each request's timeline
        # pulls its batch steps in through the links without owning
        # them.  It is open from here to the step's record, which
        # carries its trace_id/span; steps overlap, so it is no ambient
        # context
        links = [{"trace_id": s.req.trace.trace_id,
                  "span": s.req.trace.span_id}
                 for s in plan.seqs if s.req.trace is not None]
        span = step.span = _tracing.start_span(
            "batch_step", links=links or None,
            attrs={"engine": self.engine_id})
        try:
            nan_lane = self._maybe_poison(plan)
            n_progs = len(self._programs)
            prog = self._program(plan.q_width)
            if len(self._programs) > n_progs:
                step.cold = self._dispatch_cold = True   # noqa: PTL902 — GIL-atomic bool, sole loop-thread writer; the watchdog tolerates one stale poll of the compile-grace flag
            # chaos NaN injection rides a logits bias vector: 0
            # everywhere (jit-compiled no-op add) except the poisoned
            # lane
            poison = np.zeros((self.max_batch,), "float32")
            if nan_lane is not None:
                poison[nan_lane] = np.nan
            prev = self._no_prev if unread is None else unread.nxt
            phases.switch(_DISPATCH)
            step.call_at = time.monotonic()
            step.nxt, pools, rng = prog(
                self._params, plan.tok, plan.pos, pools_in, plan.page_ids,
                plan.slots, plan.kv_lens, plan.q_lens, plan.tables,
                plan.temps, key_in, poison, prev, plan.take)
        except BaseException:
            span.end(status="error")
            raise
        with self._lock:
            if epoch != self._epoch:
                span.end(status="error")
                return False  # watchdog relaunched mid-dispatch: zombie
                              # result — the fresh epoch re-runs the work
            # the next dispatch takes these, whether or not this step's
            # tokens have been read by then
            self._pools, self._key = pools, rng
            if step.cold:
                # the call returned, so the program is compiled: from
                # here the step is the device's, and the watchdog's
                # plain budget counts from now
                self._dispatch_cold = False
                self._dispatch_t0 = step.bracket_t0 = time.monotonic()
            if unread is None:
                self._n_drained += 1
            else:
                self._n_ahead += 1
        step.front = phases.dispatched(_PLAN if unread is None
                                       else _HOST_READ)
        return True

    def _land_step(self, flight: _Flight, behind: Optional[_Flight],
                   epoch: int, phases: _LoopPhases) -> None:
        """Read ``flight``'s sampled tokens, commit it, emit its tokens
        and write its record.  ``behind``: the step dispatched after it
        and still unread, which inherits the watchdog's bracket."""
        plan = flight.plan
        phases.switch(_HOST_READ)
        # THE boundary sync: exactly one device read per step —
        # admission, eviction and EOS all key off it
        toks = np.asarray(flight.nxt)  # noqa: PTL701 — window boundary
        # what this step cost the loop: from its own dispatch call, or
        # from its predecessor's result where that came later (the
        # device was still on the predecessor), to its result.  Steps
        # that follow each other tile the loop's time
        now = time.monotonic()
        step_s = now - max(flight.call_at, phases.result_at)
        phases.result_at = now
        self._h_step.observe(step_s)
        phases.switch(_COMMIT)
        # dispatch-stream markers: the REAL fed-token count (the
        # prefix-cache FLOPs-skip proof reads it), and the iteration
        # count the host read covered, so the one-read-per-window test
        # measures it
        _mark_op_stream("serving_prefill", plan.fed_prefill)
        _mark_op_stream("serving_host_sync", 1)
        with self._wake:
            if epoch != self._epoch:
                flight.span.end(status="error")
                return    # watchdog relaunched mid-dispatch: zombie
                          # result — the fresh epoch re-runs the work
            self._pass_bracket_locked(behind)
            self.scheduler.commit(plan)
            group = plan.bisect_group
            if group is not None:
                # this probe batch ran clean: its members are proven
                # innocent — retire the group and, once every group
                # resolved, close the quarantine episode
                self.scheduler.bisect_done(group)
                if not self.scheduler.bisect_groups:
                    self._end_quarantine_locked(
                        "bisection episode resolved")
            self._note_clean_step_locked()
            self._c_steps.inc()
            self._c_dispatch.inc()
            self._c_prefill.inc(plan.fed_prefill)
            state = self._state_counts(plan)
            self._state_lanes += state[0]
            self._state_resets += state[1]
            self._scan_rows += state[2]
            blocks = self._attn_blocks(plan)
            self._n_attn_blocks += blocks
            tiles = self._attn_tiles(plan)
            self._n_attn_tiles += tiles[0]
            self._n_attn_tile_slots += tiles[1]
            keys = self._keys_attended(plan)
            self._attn_keys_full += keys[0]
            self._attn_keys_window += keys[1]
            select = self._select_counts(plan)
            self._select_rows += select[0]
            self._keys_visible += select[1]
            self._keys_selected += select[2]
            kernel_layers = self._expert_kernel_layers(plan)
            self._n_expert_kernel_layers += kernel_layers
            scan_layers = self._scan_kernel_layers(plan)
            self._n_scan_kernel_layers += scan_layers
            now = time.monotonic()
            for i, seq in enumerate(plan.seqs):
                if seq.req.done:
                    # finished mid-step (stop(), a cancel, an error) —
                    # or by the step before this one: a lane with an
                    # eos_token_id was fed on as if its unread token
                    # were not the end, and this is the row to drop
                    continue
                if seq.kv_len < len(seq.tokens):
                    continue        # chunked prefill still in flight
                req = seq.req
                tok_i = int(toks[i])
                if tok_i < 0:
                    # on-device NaN-logits sentinel tripped for this
                    # lane (injected or genuine): quarantine it alone
                    # — co-batched lanes never mix activations, so
                    # the rest of the batch is sound
                    self._quarantine_locked(
                        seq, reason="nan_logits",
                        batch=len(plan.seqs))
                    continue
                seq.tokens.append(tok_i)
                req._emit(tok_i)
                self._c_decode.inc()
                if len(req.tokens) == 1:
                    self._h_ttft.observe(now - req.submitted_at)
                eos = req.eos_token_id
                if (eos is not None and tok_i == eos) or \
                        len(req.tokens) >= req.max_new_tokens:
                    if self.prefix_cache is not None and \
                            not seq.cache_inserted:
                        self._cache_prompt(seq)
                    self.scheduler.finish(seq)
                    self._h_latency.observe(now - req.submitted_at)
                elif self.prefix_cache is not None and \
                        not seq.cache_inserted:
                    self._cache_prompt(seq)
            self._g_occ.set(len(self.scheduler.running))
            self._emit_batch_step(
                phases.take(flight.front), plan, plan.n_prefill,
                int(plan.q_width), plan.fed_prefill + plan.fed_decode,
                step_s, flight.cold, 1, "single_step",
                routing=toks[self.max_batch:], ahead=flight.ahead,
                span=flight.span, state=state, attn_blocks=blocks,
                attn_tiles=tiles, attn_keys=keys, select=select,
                expert_kernel_layers=kernel_layers,
                scan_kernel_layers=scan_layers)
        flight.span.end()

    def _state_counts(self, plan):
        """``(state_lanes, state_resets, scan_rows)``: the lanes whose
        state this step read and wrote, those among them that started at
        position 0 (the step zeroed their state), and the rows that went
        through a linear-attention layer's chunked form (lanes that fed
        more than one), from the plan's lengths alone; zeros for a model
        with no state layer, and no rows for one with no linear-attention
        layer."""
        if not self._n_state:
            return 0, 0, 0
        q = plan.q_lens
        return (int((q > 0).sum()),
                int(((q > 0) & (plan.kv_lens == q)).sum()),
                int(q[q > 1].sum()) if self._linear_heads else 0)

    def _select_counts(self, plan):
        """``(select_rows, keys_visible, keys_selected)`` of ONE latent
        layer with an index: the step's rows that saw more keys than the
        index keeps (and so chose among them), and the keys the step's
        rows saw and kept, summed over its real rows — a row at position
        ``p`` sees ``p + 1`` keys and keeps ``min(top_k, p + 1)`` — from
        the plan's lengths alone; zeros for a model with no such
        layer."""
        top_k = self._index_topk
        if not top_k:
            return 0, 0, 0
        q = plan.q_lens.astype("int64")
        before = plan.kv_lens.astype("int64") - q     # keys behind a chunk
        visible = q * before + q * (q + 1) // 2
        over = np.clip(before + q - top_k, 0, q)      # rows that chose
        excess = over * np.maximum(before - top_k, 0) + over * (over + 1) // 2
        return (int(over.sum()), int(visible.sum()),
                int((visible - excess).sum()))

    def _pages_read(self, plan):
        """``(window_pages_read, full_pages_read)``: the pages the
        attention kernels of this step's window and full layers were
        asked to visit, summed over lanes and layers, from the plan's
        lengths alone (host arithmetic, no device work)."""
        live = plan.q_lens > 0
        kv = plan.kv_lens[live].astype("int64")
        ps = self._page_size
        last = (kv - 1) // ps
        n_window = self._cache.n_window
        full = int((last + 1).sum()) * self._cache.n_full
        if not n_window:
            return 0, full
        oldest = kv - plan.q_lens[live] - (self._cache.window - 1)
        first = np.maximum(oldest, 0) // ps
        return int((last - first + 1).sum()) * n_window, full

    def _attn_blocks(self, plan, steps: int = 1) -> int:
        """The key blocks this step's attention kernels walk, summed
        over lanes, query tiles and layers, by the kernel's own bounds
        (``ragged_paged_attention.walk_blocks``) on the plan's lengths:
        host arithmetic, no device work.  Kernel seconds over it is the
        cost of a block.  ``steps``: a fused window's iterations, each a
        token further."""
        from ..ops.pallas.ragged_paged_attention import walk_blocks
        return sum(
            layers * walk_blocks(
                plan.kv_lens + j, plan.q_lens, int(plan.q_width),
                heads, width, self._itemsize, self._page_size,
                self._ring_pages, window)
            for (width, window, heads), layers in self._attn_launches
            for j in range(steps))

    def _attn_tiles(self, plan, steps: int = 1):
        """``(attn_tiles, attn_tile_slots)``: the query tiles this step's
        attention launches work on and the grid steps they have, summed
        over layers, by the kernel's own tiling
        (``ragged_paged_attention.launch_tiles``) on the plan's
        ``q_lens`` and the program's rows: host arithmetic.  Their ratio
        is how much of the grid works.  ``steps``: a fused window's
        iterations, each the same launches."""
        from ..ops.pallas.ragged_paged_attention import launch_tiles
        tiles = slots = 0
        for (width, window, heads), layers in self._attn_launches:
            live, grid = launch_tiles(
                plan.q_lens, plan.rows, int(plan.q_width), heads,
                width, self._itemsize, self._page_size, self._ring_pages,
                window)
            tiles += layers * steps * live
            slots += layers * steps * grid
        return tiles, slots

    def _keys_attended(self, plan, steps: int = 1):
        """``(attn_keys_full, attn_keys_window)``: the keys this step's
        real rows attend, summed over rows and over the layers of each
        kind — a row at position ``p`` attends ``p + 1`` keys in a full
        layer and ``min(window, p + 1)`` in a window layer — from the
        plan's lengths alone: host arithmetic.  ``steps``: a fused
        window's iterations, each a token further."""
        full = window = 0
        for j in range(steps):
            q = plan.q_lens.astype("int64")
            start = plan.kv_lens.astype("int64") + j - q    # first position
            if self._cache.n_full:
                full += int((q * start + q * (q + 1) // 2).sum())
            if self._cache.n_window:
                w = self._cache.window
                # rows whose p + 1 <= w attend p + 1, the rest w
                under = np.clip(w - start, 0, q)
                window += int((under * start + under * (under + 1) // 2
                               + (q - under) * w).sum())
        return full * self._cache.n_full, window * self._cache.n_window

    def _expert_kernel_layers(self, plan) -> int:
        """The step's expert layers whose taken branches are the kernel
        that reads a picked expert's weights itself, by the op's own
        shape rule (``ops.routed_experts.narrow_step``) on the program's
        rows: static a program, so host arithmetic.  All of them in a
        decode-only step, none in a step with a chunk."""
        from ..ops.routed_experts import narrow_step
        return sum(layers for top_k, layers in self._expert_layers
                   if narrow_step(plan.rows, top_k))

    def _scan_kernel_layers(self, plan) -> int:
        """The step's linear-attention layers whose chunked form ran as
        the kernel that keeps a sequence's state in VMEM, by the op's own
        rule: a program wider than a token holds the chunked form
        (``generation._linear_attention_rows``), and the kernel takes
        the layer's heads (``ops.pallas.gated_delta_scan.takes``).  Host
        arithmetic: all of them in a step with a chunk, none in a
        decode-only step."""
        from ..ops.pallas.gated_delta_scan import takes
        return sum(takes(heads) for heads in self._linear_heads) \
            if plan.q_width > 1 else 0

    def _emit_batch_step(self, phase_seconds, plan, prefill_seqs,
                         q_width, tokens, step_s, cold_start,
                         fused_steps, exit_reason, routing=(),
                         ahead=False, span=None, state=(0, 0, 0),
                         attn_blocks=0, attn_tiles=(0, 0),
                         attn_keys=(0, 0), select=(0, 0, 0),
                         expert_kernel_layers=0,
                         scan_kernel_layers=0) -> None:
        """The step's ``batch_step`` record (under ``_wake``;
        ``phase_seconds`` from ``_LoopPhases.take``; ``span`` the step's
        own where no ambient one covers it).  step_s +
        page_occupancy make each record a ready-made (features, seconds)
        sample for the learned perf model (analysis.perf_features /
        tuning.learned); cold_start marks the program-cache-miss steps
        whose step_s is trace+compile, not steady-state work — the
        featurizer and the divergence watchdog skip them."""
        if not _events.enabled():
            return
        plan_s, prepare_s, dispatch_s, read_s, commit_s, host_gap_s, \
            wait_s, admit_queue_s = phase_seconds
        expert_rows, expert_rows_max, experts_hit = \
            (int(v) for v in routing) if len(routing) else (0, 0, 0)
        window_pages, full_pages = self._pages_read(plan)
        _events.emit("batch_step", batch=len(plan.seqs),
                     prefill_seqs=prefill_seqs,
                     decode_seqs=plan.n_decode, q_width=q_width,
                     tokens=tokens, rows=plan.rows * fused_steps,
                     prefill_waiting=plan.prefill_waiting,
                     queue_depth=self.scheduler.queue_depth(),
                     step_s=round(step_s, 6),
                     cold_start=cold_start or None,
                     ahead=ahead or None,
                     trace_id=span.trace_id if span is not None else None,
                     span=span.span_id if span is not None else None,
                     page_occupancy=round(
                         1.0 - self.pool.available()
                         / max(self.pool.num_pages - 1, 1), 4),
                     fused_steps=fused_steps, exit_reason=exit_reason,
                     plan_s=plan_s, prepare_s=prepare_s,
                     dispatch_s=dispatch_s, read_s=read_s,
                     commit_s=commit_s, host_gap_s=host_gap_s,
                     wait_s=wait_s, admit_queue_s=admit_queue_s,
                     expert_rows=expert_rows,
                     expert_rows_max=expert_rows_max,
                     experts_hit=experts_hit,
                     expert_kernel_layers=expert_kernel_layers,
                     scan_kernel_layers=scan_kernel_layers,
                     window_pages_read=window_pages,
                     full_pages_read=full_pages,
                     attn_blocks=attn_blocks,
                     attn_tiles=attn_tiles[0],
                     attn_tile_slots=attn_tiles[1],
                     attn_keys_full=attn_keys[0],
                     attn_keys_window=attn_keys[1],
                     state_lanes=state[0], state_resets=state[1],
                     scan_rows=state[2], select_rows=select[0],
                     keys_visible=select[1], keys_selected=select[2])

    def _run_window(self, plan, w, max_window, clamp_reason,
                    epoch: int, phases: _LoopPhases):
        """Fused serving window: up to ``w`` decode iterations in one
        compiled dispatch (same shared batch_step span contract as
        ``_dispatch_step``)."""
        links = [{"trace_id": s.req.trace.trace_id,
                  "span": s.req.trace.span_id}
                 for s in plan.seqs if s.req.trace is not None]
        with _tracing.trace_span("batch_step", links=links or None,
                                 attrs={"engine": self.engine_id,
                                        "fused": True}):
            self._run_window_traced(plan, w, max_window, clamp_reason,
                                    epoch, phases)

    def _run_window_traced(self, plan, w, max_window, clamp_reason,
                           epoch: int, phases: _LoopPhases):
        # snapshot the device state FIRST (see _dispatch_step): a
        # zombie thread must only ever write into these captured,
        # abandoned buffers after a watchdog relaunch
        pools_in, key_in = self._pools, self._key  # noqa: PTL902 — zombie-containment snapshot (window path), same contract as _dispatch_step
        # the fused program has no poison vector input, so "nan"
        # poison degrades to a pre-dispatch raise here — the failure
        # still quarantines through the same bisection (which pins the
        # single-step path, where the on-device sentinel takes over)
        for i, seq in enumerate(plan.seqs):
            if seq.req.id in self._poison:
                raise _faults.InjectedFault(
                    f"injected serving_step poison "
                    f"(request {seq.req.id})")
        _faults.maybe_fault("serving_step")
        directive = _faults.take_serving_poison()
        if directive is not None and plan.seqs:
            self._poison[plan.seqs[0].req.id] = directive
            raise _faults.InjectedFault(
                f"injected serving_step poison "
                f"(request {plan.seqs[0].req.id})")
        b = self.max_batch
        n_progs = len(self._programs)
        prog = self._window_program(max_window)
        cold_start = len(self._programs) > n_progs
        if cold_start:
            self._dispatch_cold = True   # noqa: PTL902 — GIL-atomic bool, sole loop-thread writer; the watchdog tolerates one stale poll of the compile-grace flag
        # PRE-append lengths: the committed KV, not the plan's
        # post-step kv_lens — the compiled loop owns the append cursor
        kv0 = (plan.kv_lens - plan.q_lens).astype("int32")
        live = plan.q_lens > 0
        # a decode-only plan has one row a lane, the live lanes first
        tok0 = plan.tok.astype("int32")
        eos_ids = np.full((b,), -1, "int32")     # -1 never samples
        budgets = np.full((b,), 2 ** 30, "int32")
        for i, seq in enumerate(plan.seqs):
            eos = seq.req.eos_token_id
            eos_ids[i] = -1 if eos is None else int(eos)
            budgets[i] = seq.req.max_new_tokens - len(seq.req.tokens)
        phases.switch(_DISPATCH)
        with self._h_step.time() as step_timer:
            packed, pools, rng = prog(
                self._params, tok0, pools_in, kv0, live,
                plan.tables, plan.temps, eos_ids, budgets, key_in,
                np.int32(w))
            # double-buffered plan: the device is running the window —
            # pre-stage the next boundary's admission work NOW, while
            # the host is otherwise idle (async dispatch means the
            # blocking read below is where the wait happens).  It is
            # planning, so plan_s of a window counts it, though it lies
            # outside host_gap_s
            phases.switch(_PLAN)
            with self._wake:
                if epoch == self._epoch:
                    self.scheduler.prestage_plan(plan, w)
            phases.switch(_HOST_READ)
            # THE boundary sync: ONE packed device read per fused
            # window — tokens, finished mask and iteration count ride
            # a single int32 array
            out = np.asarray(packed)  # noqa: PTL701 — window boundary
        phases.result_at = time.monotonic()
        # a window is read where it was dispatched: both halves of its
        # record are taken here, the pre-staged plan in its plan_s
        front = phases.dispatched(_COMMIT)
        steps = int(out[0, max_window + 1])
        fed = len(plan.seqs) * steps
        _mark_op_stream("serving_host_sync", steps)
        with self._wake:
            if epoch != self._epoch:
                return    # zombie window result after a relaunch
            self._pools, self._key = pools, rng
            self._n_drained += 1
            self._note_clean_step_locked(steps)
            self.scheduler.commit_window(plan, steps)
            self._c_steps.inc(steps)
            self._c_dispatch.inc()
            now = time.monotonic()
            any_finished = False
            for i, seq in enumerate(plan.seqs):
                if seq.req.done:
                    continue        # finished (stop()/error) mid-step
                req = seq.req
                first = len(req.tokens) == 0
                for j in range(steps):
                    tok_i = int(out[i, j])
                    seq.tokens.append(tok_i)
                    req._emit(tok_i)
                self._c_decode.inc(steps)
                if first:
                    self._h_ttft.observe(now - req.submitted_at)
                if self.prefix_cache is not None and \
                        not seq.cache_inserted:
                    self._cache_prompt(seq)
                if out[i, max_window]:
                    any_finished = True
                    self.scheduler.finish(seq)
                    self._h_latency.observe(now - req.submitted_at)
            self._g_occ.set(len(self.scheduler.running))
            blocks = self._attn_blocks(plan, steps)
            self._n_attn_blocks += blocks
            tiles = self._attn_tiles(plan, steps)
            self._n_attn_tiles += tiles[0]
            self._n_attn_tile_slots += tiles[1]
            keys = self._keys_attended(plan, steps)
            self._attn_keys_full += keys[0]
            self._attn_keys_window += keys[1]
            self._emit_batch_step(
                phases.take(front), plan, 0, 1, fed, step_timer.seconds,
                cold_start, steps,
                "finished" if any_finished else clamp_reason,
                attn_blocks=blocks, attn_tiles=tiles, attn_keys=keys)

    def _cache_prompt(self, seq):
        """Share the finished prompt's full pages through the prefix
        cache (once per admission; pages the sequence itself borrowed
        from the cache are skipped)."""
        self.prefix_cache.insert(seq.req.prompt, seq.pages,
                                 shared=seq.shared)
        seq.cache_inserted = True

    # -- fault containment: quarantine bisection -------------------------
    @staticmethod
    def _prompt_hash(prompt) -> str:
        return hashlib.sha256(
            ",".join(map(str, prompt)).encode()).hexdigest()[:16]

    def _contain_step_failure(self, plan, exc, epoch: int) -> None:
        """A WARM dispatch raised before it consumed its inputs (the
        caller routes cold dispatches and failures that arrive after
        the pools were donated to the engine-level failure path; off
        CPU the pools are donated, so a retry is only sound while they
        are still alive).  Nothing was committed (tokens only land
        after the boundary read), so re-feeding the same chunks to the
        same pages is idempotent — instead of failing the whole batch,
        split its live members in half and probe each half as its own
        restricted plan until the offender is alone."""
        with self._wake:
            if epoch != self._epoch:
                return              # a relaunch already superseded us
            self._clean_steps = 0
            group = plan.bisect_group
            if group is not None:
                self.scheduler.bisect_done(group)
            live = [s for s in plan.seqs
                    if s in self.scheduler.running and not s.req.done]
            if len(live) <= 1:
                # isolated (or the batch emptied mid-flight): the
                # offender fails ALONE; everyone else was or will be
                # proven innocent by their own clean probe
                for seq in live:
                    self._quarantine_locked(
                        seq,
                        reason=f"step failure: "
                               f"{type(exc).__name__}: {exc}",
                        batch=len(plan.seqs))
                if not self.scheduler.bisect_groups:
                    self._end_quarantine_locked("offender isolated")
                return
            if self.health in ("ok", "degraded"):
                self._set_health(
                    "quarantining",
                    f"step failed over {len(live)} requests "
                    f"({type(exc).__name__}) — bisecting")
            ids = [s.req.id for s in live]
            mid = len(ids) // 2
            self.scheduler.bisect_push_front([ids[:mid], ids[mid:]])

    def _quarantine_locked(self, seq, reason: str, batch: int) -> None:
        req = seq.req
        h = self._prompt_hash(req.prompt)
        self._quarantined[h] = self._quarantined.get(h, 0) + 1
        self._poison.pop(req.id, None)
        self._n_quarantined += 1
        self._c_quarantined.inc()
        _events.emit("quarantine", request=req.id, reason=reason,
                     prompt_hash=h, action="quarantined", batch=batch)
        req.error_kind = "quarantined"
        self.scheduler.finish(
            seq, error=f"request quarantined: {reason}")
        self._g_occ.set(len(self.scheduler.running))

    # -- fault containment: health state machine -------------------------
    def _set_health(self, state: str, reason: str) -> None:
        prev = self.health
        if state == prev:
            return
        self.health = state
        self._clean_steps = 0
        self._g_health.set(_HEALTH_RANK[state])
        _events.emit("health_transition", engine=self.engine_id,
                     previous=prev, state=state, reason=reason)

    def _end_quarantine_locked(self, reason: str) -> None:
        if self.health == "quarantining":
            self._set_health("degraded", reason)

    def _note_clean_step_locked(self, n: int = 1) -> None:
        self._clean_steps += int(n)
        if self.health == "degraded" \
                and self._clean_steps >= self.health_recovery_steps:
            self._set_health(
                "ok", f"{self._clean_steps} clean steps")

    def _fail_all_locked(self, error: str) -> None:
        leftovers = list(self.scheduler.waiting) \
            + list(self.scheduler.running)
        self.scheduler.waiting.clear()
        for seq in leftovers:
            seq.req.error_kind = seq.req.error_kind or "unhealthy"
            self.scheduler.finish(seq, error=error)
        self._g_queue.set(0)
        self._g_occ.set(0)

    # -- fault containment: hung-step watchdog ---------------------------
    def _watchdog_loop(self, timeout: float) -> None:
        poll = max(min(timeout / 4.0, 0.25), 0.01)
        while True:
            with self._lock:
                if not self._running:
                    return
                t0 = self._dispatch_t0
                budget = timeout + (_COLD_DISPATCH_GRACE_S
                                    if self._dispatch_cold else 0.0)
            if t0 is not None and time.monotonic() - t0 > budget:
                self._recover_from_stall(timeout)
            time.sleep(poll)

    def _recover_from_stall(self, timeout: float) -> None:
        """A device dispatch exceeded the watchdog budget: dump the
        flight recorder, abandon the wedged epoch (thread, device
        pools, page accounting) and relaunch with every survivor
        requeued at the FRONT — the eviction-resume contract replays
        their prompt+generated tokens, so no stream truncates."""
        with self._wake:
            t0 = self._dispatch_t0
            budget = timeout + (_COLD_DISPATCH_GRACE_S
                                if self._dispatch_cold else 0.0)
            if t0 is None or time.monotonic() - t0 <= budget:
                return          # resolved while we were scheduled
            age = time.monotonic() - t0
            plan = self._dispatch_plan
            self._relaunches += 1
            self._clean_steps = 0
            self._c_step_timeout.inc()
            _events.emit(
                "step_timeout", engine=self.engine_id,
                age_s=round(age, 3), timeout_s=float(timeout),
                batch=len(plan.seqs) if plan is not None else 0,
                relaunches=self._relaunches)
            _tracing.dump_flight("serving_step_timeout")
            if self._relaunches > self.max_watchdog_relaunches:
                # a dispatch that hangs this persistently is not
                # coming back: stop relaunching, fail LOUDLY and let
                # the fleet supervisor restart the whole replica
                self._epoch += 1
                self._dispatch_t0 = None
                self._dispatch_plan = None
                self._accepting = False
                self._set_health(
                    "failed",
                    f"{self._relaunches} watchdog relaunches exceed "
                    f"the cap ({self.max_watchdog_relaunches})")
                self._fail_all_locked(
                    "engine failed: repeated hung steps")
                self._wake.notify_all()
                return
            self._set_health(
                "degraded",
                f"hung step ({age:.1f}s > {timeout}s) — relaunching "
                f"the iteration loop")
            self._relaunch_locked()

    def _relaunch_locked(self) -> None:
        self._epoch += 1
        epoch = self._epoch
        self._dispatch_t0 = None
        self._dispatch_plan = None
        # requeue EVERY running sequence at the front, generated
        # tokens kept: re-admission re-prefills prompt+generated and
        # continues token-exact (greedy), exactly like an eviction
        for seq in reversed(list(self.scheduler.running)):
            seq.pages = []      # the pool they point into is dead
            seq.shared = set()
            seq.kv_len = 0
            seq.cached_tokens = 0
            seq.cache_inserted = False
            seq.req.evictions += 1
            self.scheduler.evictions += 1
            self.scheduler.waiting.appendleft(seq)
        self.scheduler.running.clear()
        # fresh page accounting + DEVICE pools: the wedged dispatch
        # may still be writing into the old buffers, so they are
        # abandoned, never reused (the zombie thread's results are
        # fenced off by the epoch check at every commit point)
        self.pool = PagePool(self._num_pages, self._page_size)
        self.prefix_cache = PrefixCache(self.pool) \
            if self._prefix_caching else None
        self.scheduler.rebind_pool(self.pool, self.prefix_cache)
        self._pools = self._new_pools()
        self._thread = threading.Thread(
            target=self._loop, args=(epoch,), daemon=True,
            name=f"serving-engine-{self.engine_id}-e{epoch}")
        self._thread.start()
        self._wake.notify_all()

    # -- fault containment: deadlines + cancellation ---------------------
    def _cancel_request(self, req, reason: str) -> None:
        """``Request.cancel()`` hook: routes through the engine lock
        so pages and the batch slot free immediately."""
        with self._wake:
            self._cancel_locked(req, reason)

    def _cancel_locked(self, req, reason: str) -> None:
        if req.done:
            return
        req.error_kind = req.error_kind or "cancelled"
        self._n_cancelled += 1
        self._c_cancelled.inc()
        _events.emit("request_cancelled", request=req.id,
                     reason=reason, n_tokens=len(req.tokens),
                     deadline_s=req.deadline_s)
        self.scheduler.drop(req, error=reason)
        self._g_queue.set(self.scheduler.queue_depth())
        self._g_occ.set(len(self.scheduler.running))

    def _sweep_deadlines_locked(self) -> None:
        now = time.monotonic()
        expired = [s.req for s in (list(self.scheduler.running)
                                   + list(self.scheduler.waiting))
                   if s.req.deadline_at is not None
                   and now > s.req.deadline_at]
        for req in expired:
            req.error_kind = "deadline"
            self._cancel_locked(
                req, f"deadline exceeded ({req.deadline_s}s)")

    # -- the jitted ragged program ---------------------------------------
    def _program(self, qw: int):
        import jax
        import jax.numpy as jnp
        from ..flags import get_flag
        key = (qw, bool(get_flag("use_pallas_ragged_attention")),
               bool(get_flag("use_pallas_fused_decode")),
               bool(get_flag("pallas_interpret")))
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        step = self._step_fn
        routed = self._routed

        def program(params, tok, pos, pools, page_ids, slots, kv_lens,
                    q_lens, tables, temps, rng, poison, prev, take):
            # tok, pos, page_ids, slots, take: [step_rows(qw,
            # max_batch)], the plan's packed rows.  prev: the sampled
            # row of the step dispatched before this one, as it left
            # that program, which the host may not have read yet; a row
            # with take >= 0 feeds lane take's token of it.  (A lane
            # that tripped the sentinel there holds -1: fed as token 0,
            # and the host throws its output away when it reads the -1.)
            # One program a width serves both forms: with nothing
            # unread take is -1 throughout and prev a resident zero row
            # everything around the step is the trace's part "sample"
            # (models.generation.STEP_PARTS)
            with jax.named_scope("sample"):
                tok = jnp.where(take >= 0,
                                jnp.maximum(prev[jnp.maximum(take, 0)], 0),
                                tok.astype(jnp.int32))
            out = step.packed(params, tok, pos, pools, page_ids, slots,
                              kv_lens, q_lens, tables, qw)
            logits, pools = out[0], out[1]
            with jax.named_scope("sample"):
                # chaos bias (zeros in production — a no-op add) lets the
                # fault injector NaN one lane's logits without a host
                # hook
                logits = logits + poison[:, None]
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                rng, sub = jax.random.split(rng)
                t32 = temps.astype(jnp.float32)
                scaled = logits.astype(jnp.float32) \
                    / jnp.maximum(t32, jnp.float32(1e-6))[:, None]
                sampled = jax.random.categorical(sub, scaled, axis=-1) \
                    .astype(jnp.int32)
                nxt = jnp.where(t32 > jnp.float32(0.0), sampled, greedy)
                # on-device NaN-logits sentinel: a NaN row (injected or a
                # genuine numeric blow-up) collapses the sampled token to
                # -1, so the host's ONE boundary read doubles as the
                # detector and the lane quarantines with no extra sync
                bad = jnp.isnan(logits).any(axis=-1)
                nxt = jnp.where(bad, jnp.int32(-1), nxt)
                if routed:
                    # a model with expert layers: its step's routing
                    # counts ride behind the sampled tokens in the one
                    # array the host reads (no other model's program has
                    # this branch)
                    nxt = jnp.concatenate([nxt, out[2].astype(jnp.int32)])
            return nxt, pools, rng

        # the name the program carries in a profiler trace and in HLO
        program.__name__ = f"serve_step_q{qw}"
        # pools are index 3; donated, so each output pool aliases its
        # input buffer (CPU has no donation support).  Donation alone
        # does not keep a step from copying them: the k/v write has to
        # leave each pool in the layout the ragged kernel takes, which
        # generation._scatter_pages does and tests/test_smoke_chip.py
        # guards on a described v5e
        donate = (3,) if jax.default_backend() != "cpu" else ()
        prog = jax.jit(program, donate_argnums=donate)
        self._programs[key] = prog
        return prog

    def _window_program(self, max_window: int):
        """The fused-window program (``build_fused_window_step``),
        cached per static ``max_window``: the scheduler's clamped
        width rides as a TRACED scalar, so one compiled loop serves
        every window length up to the flag value."""
        import jax
        from ..flags import get_flag
        key = ("window", int(max_window),
               bool(get_flag("use_pallas_ragged_attention")),
               bool(get_flag("use_pallas_fused_decode")),
               bool(get_flag("pallas_interpret")))
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        _, window = self.model.build_fused_window_step(int(max_window))
        window.__name__ = f"serve_window_w{int(max_window)}"
        # pools are index 2; donated like the single-step program
        donate = (2,) if jax.default_backend() != "cpu" else ()
        prog = jax.jit(window, donate_argnums=donate)
        self._programs[key] = prog
        return prog

    # -- introspection ---------------------------------------------------
    def stats(self) -> dict:
        out = {"engine": self.engine_id,
               "queue_depth": self.scheduler.queue_depth(),
               "running": len(self.scheduler.running),
               "evictions": self.scheduler.evictions,
               "deferred_admissions":
                   self.scheduler.deferred_admissions,
               "prestaged_plans": self.scheduler.prestaged_plans,
               "prestage_commits": self.scheduler.prestage_commits,
               "prestage_discards": self.scheduler.prestage_discards,
               "step_rows": self.scheduler.rows_planned,
               "step_rows_empty": self.scheduler.rows_empty,
               "prefill_waits": self.scheduler.prefill_waits,
               "steps_ahead": self._n_ahead,  # noqa: PTL902 — advisory snapshot (see below)
               "steps_drained": self._n_drained,  # noqa: PTL902 — advisory snapshot (see below)
               "state_lanes": self._state_lanes,  # noqa: PTL902 — advisory snapshot (see below)
               "state_resets": self._state_resets,  # noqa: PTL902 — advisory snapshot (see below)
               "scan_rows": self._scan_rows,  # noqa: PTL902 — advisory snapshot (see below)
               "attn_blocks": self._n_attn_blocks,  # noqa: PTL902 — advisory snapshot (see below)
               "attn_tiles": self._n_attn_tiles,  # noqa: PTL902 — advisory snapshot (see below)
               "attn_tile_slots": self._n_attn_tile_slots,  # noqa: PTL902 — advisory snapshot (see below)
               "attn_keys_full": self._attn_keys_full,  # noqa: PTL902 — advisory snapshot (see below)
               "attn_keys_window": self._attn_keys_window,  # noqa: PTL902 — advisory snapshot (see below)
               "expert_kernel_layers": self._n_expert_kernel_layers,  # noqa: PTL902 — advisory snapshot (see below)
               "scan_kernel_layers": self._n_scan_kernel_layers,  # noqa: PTL902 — advisory snapshot (see below)
               "select_rows": self._select_rows,  # noqa: PTL902 — advisory snapshot (see below)
               "keys_visible": self._keys_visible,  # noqa: PTL902 — advisory snapshot (see below)
               "keys_selected": self._keys_selected,  # noqa: PTL902 — advisory snapshot (see below)
               "free_pages": self.pool.available(),  # noqa: PTL902 — advisory snapshot; the handle swaps atomically at relaunch
               "programs": len(self._programs),
               "health": self.health,
               "quarantined": self._n_quarantined,  # noqa: PTL902 — stats() is an advisory lock-free snapshot; counters are GIL-atomic ints
               "quarantined_prompts": len(self._quarantined),
               "cancelled": self._n_cancelled,  # noqa: PTL902 — advisory snapshot (see above)
               "watchdog_relaunches": self._relaunches,  # noqa: PTL902 — advisory snapshot (see above)
               "wedged_threads": self._wedged_threads}
        if self.prefix_cache is not None:  # noqa: PTL902 — advisory snapshot; the handle swaps atomically at relaunch
            out["prefix_cache"] = self.prefix_cache.stats()
        return out
