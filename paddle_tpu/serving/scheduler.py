"""Continuous-batching scheduler — iteration-level request admission
over a refcounted KV page pool (Orca-style, the scheduling shape
*Ragged Paged Attention* [arXiv 2604.15464] makes cheap on TPU).

The reference ecosystem schedules serving batches at REQUEST
granularity (a batch runs to completion before the next forms); this
scheduler re-plans every token iteration:

* a new request joins the running batch the moment a slot and enough
  pages exist — its prompt prefills in the same ragged step other
  requests decode in;
* a finished request (EOS or budget) frees its pages IMMEDIATELY, so
  the next iteration can admit;
* page exhaustion evicts the youngest running request (fewest sunk
  tokens) and requeues it at the FRONT of the wait queue — its
  generated-so-far tokens are kept, so re-admission re-prefills
  prompt+generated and continues where it stopped;
* prompt prefixes already resident (``prefix_cache``) are shared by
  refcount instead of recomputed.

Everything here is HOST bookkeeping over python ints (free lists, page
tables, token lists).  The device arrays ride in the
:class:`StepPlan`; the engine owns the jitted step.  Step-loop code
paths must not read device values back (PTL701) — the engine's single
per-iteration boundary sync is the only sanctioned read.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PagePool", "Request", "Scheduler", "StepPlan", "step_rows"]


def _bucket(n: int) -> int:
    """Next power of two >= n — bounds program-compile count to
    log2(max prompt length) buckets."""
    b = 1
    while b < n:
        b <<= 1
    return b


# Up to about this many rows a step's time is the read of the weights
# and rows cost nothing (float32 weights on a v5e: 819 GB/s against one
# bf16 pass at 197 TFLOP/s is ~480 rows, at "high" ~160).  With fewer, a
# narrow program would have no room for a second short chunk, and a
# burst of short prompts would prefill one a step at a decode step's
# price each
_MIN_PREFILL_ROWS = 128


def step_rows(q_width: int, max_batch: int) -> int:
    """Token rows of the step program of attention width ``q_width`` (a
    power of two, :func:`_bucket` of the step's widest chunk): a
    function of the program's key alone, so that a request sent alone
    compiles exactly the program a mixed step of the same width runs.
    A decode-only step is one row a lane.  A wider one has the smallest
    multiple of 8 rows that holds one chunk of ``q_width`` beside
    ``max_batch - 1`` single tokens, and never fewer than
    ``_MIN_PREFILL_ROWS``."""
    if q_width <= 1:
        return int(max_batch)
    return max(-(-(int(q_width) + int(max_batch) - 1) // 8) * 8,
               _MIN_PREFILL_ROWS)


class PagePool:
    """Refcounted fixed-size-page allocator (host bookkeeping only —
    the device-resident pools live in the engine).

    The LAST page id is the **sink**: padding slots of a ragged step
    scatter their garbage there; it is never allocated and never
    appears in a page table."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("PagePool needs >= 2 pages (1 is the sink)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.sink = self.num_pages - 1
        self._free: List[int] = list(range(self.num_pages - 1))[::-1]
        self._refs: Dict[int, int] = {}

    def available(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self) -> int:
        """Allocate one page at refcount 1; raises on exhaustion (the
        scheduler checks ``available()`` and evicts first)."""
        if not self._free:
            raise RuntimeError("KV page pool exhausted")
        page = self._free.pop()
        self._refs[page] = 1
        return page

    def ref(self, page: int) -> None:
        if page not in self._refs:
            raise ValueError(f"page {page} is not live")
        self._refs[page] += 1

    def unref(self, page: int) -> None:
        n = self._refs.get(page)
        if not n:
            raise ValueError(f"page {page} is not live")
        if n == 1:
            del self._refs[page]
            self._free.append(page)
        else:
            self._refs[page] = n - 1


class Request:
    """One generation request: prompt in, token stream out.

    The engine pushes generated token ids into a per-request queue as
    each batch iteration completes; ``stream()`` yields them live and
    ``wait()`` blocks for the full result.  ``tokens`` accumulates the
    generated ids (prompt excluded)."""

    _IDS = itertools.count(1)

    def __init__(self, input_ids: Sequence[int], max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 temperature: float = 0.0,
                 request_id: Optional[str] = None,
                 deadline_s: Optional[float] = None):
        self.id = request_id if request_id is not None \
            else str(next(Request._IDS))
        self.prompt: List[int] = [int(t) for t in np.asarray(
            input_ids).reshape(-1)]
        if not self.prompt:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = None if eos_token_id is None \
            else int(eos_token_id)
        self.temperature = float(temperature)
        self.tokens: List[int] = []        # generated ids, in order
        self.error: Optional[str] = None
        # machine-readable failure class for the HTTP layer's status
        # mapping: "deadline"/"unhealthy" -> 503, "quarantined" -> 400,
        # "cancelled" stays in-band; None for ordinary errors
        self.error_kind: Optional[str] = None
        self._queue: "queue.Queue" = queue.Queue()
        self._done = threading.Event()
        self.submitted_at = time.monotonic()
        self.deadline_s = None if deadline_s is None \
            else float(deadline_s)
        self.deadline_at = None if deadline_s is None \
            else self.submitted_at + float(deadline_s)
        # engine-installed cancel hook: routes cancel() through the
        # engine lock so pages and the batch slot free immediately
        self._cancel_cb = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.evictions = 0
        # distributed-tracing handles (observability.tracing), set by
        # the engine at submit: the root request span ties every
        # annotation together; the queue span is open whenever the
        # request waits for admission (incl. after an eviction)
        self.trace = None              # TraceContext of the root span
        self._root_span = None
        self._queue_span = None

    # -- consumer side ---------------------------------------------------
    def stream(self, timeout: Optional[float] = 60.0):
        """Yield generated token ids as they land; returns on EOS /
        budget / failure (raises RuntimeError on failure).

        A ``timeout`` expiry CANCELS the request before raising: the
        consumer is gone, so leaving it running headless would silently
        burn batch slots and truncate the stream with no error
        anywhere — instead the engine frees its pages now and the
        failure is loud on both sides (request_cancelled event +
        RuntimeError here)."""
        while True:
            try:
                tok = self._queue.get(timeout=timeout)
            except queue.Empty:
                self.cancel(f"stream consumer timed out after "
                            f"{timeout}s without a token")
                raise RuntimeError(
                    self.error or f"request {self.id}: stream timed "
                                  f"out after {timeout}s") from None
            if tok is None:
                if self.error:
                    raise RuntimeError(self.error)
                return
            yield tok

    def wait(self, timeout: Optional[float] = 60.0) -> List[int]:
        """Block until the request finishes; returns the generated ids.
        A timeout cancels the request (see :meth:`stream`) before
        raising TimeoutError."""
        if not self._done.wait(timeout):
            self.cancel(f"wait consumer timed out after {timeout}s")
            raise TimeoutError(f"request {self.id} still running after "
                               f"{timeout}s (request cancelled)")
        if self.error:
            raise RuntimeError(self.error)
        return list(self.tokens)

    def cancel(self, reason: str = "cancelled") -> None:
        """Cancel a queued/running request: pages and the batch slot
        free immediately (via the engine's cancel hook when admitted),
        consumers see the error.  Idempotent after finish."""
        if self._done.is_set():
            return
        cb = self._cancel_cb
        if cb is not None:
            cb(self, reason)
        else:
            self.error_kind = self.error_kind or "cancelled"
            self._finish(error=reason)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    # -- engine side -----------------------------------------------------
    def _emit(self, tok: int) -> None:
        if self.first_token_at is None:
            self.first_token_at = time.monotonic()
        self.tokens.append(int(tok))
        self._queue.put(int(tok))

    def _finish(self, error: Optional[str] = None) -> None:
        # idempotent: stop() and an in-flight step can both try to
        # finish the same request; only the first one wins (a second
        # call would push a spurious None past the stream sentinel)
        if self._done.is_set():
            return
        self.error = error
        self.finished_at = time.monotonic()
        # close the trace: a queue span still open here means the
        # request died waiting (rejected / engine stopped)
        qs, self._queue_span = self._queue_span, None
        if qs is not None:
            qs.end(status="error" if error else "cancelled")
        rs, self._root_span = self._root_span, None
        if rs is not None:
            rs.end(status="error" if error else "ok", error=error,
                   n_tokens=len(self.tokens), evictions=self.evictions)
        self._done.set()
        self._queue.put(None)


class _Sequence:
    """Host decode state of one ADMITTED request: the full known token
    list (prompt + generated so far), how many of them have KV
    committed to pages, and the owned/shared page list."""

    __slots__ = ("req", "tokens", "kv_len", "pages", "shared",
                 "cached_tokens", "cache_inserted", "predicted_cost_s",
                 "slot")

    def __init__(self, req: Request):
        self.req = req
        self.tokens: List[int] = list(req.prompt) + list(req.tokens)
        self.kv_len = 0
        self.pages: List[int] = []
        self.shared: set = set()       # page ids held via prefix cache
        self.cached_tokens = 0
        self.cache_inserted = False
        # learned-model step-cost estimate at admission (None: raw
        # page/token caps decided alone); rides serving_admit events
        self.predicted_cost_s: Optional[float] = None
        # the slot this sequence owns while it runs, of max_batch: its
        # window layers write ring ``slot`` and its state layers row
        # ``slot`` (Scheduler.lane_tables); None while it waits, and for
        # a model that keeps neither
        self.slot: Optional[int] = None

    @property
    def n_generated(self) -> int:
        return len(self.req.tokens)


class StepPlan:
    """One ragged iteration, planned: the active sequences and the host
    arrays the engine feeds the jitted step.  ``tok``, ``pos``,
    ``page_ids`` and ``slots`` are ``[rows]``: sequence 0's
    ``q_lens[0]`` tokens, then sequence 1's, ..., then rows that carry
    no token (token 0, the sink page).  ``q_width`` is the attention
    width of the step's program, the power-of-two bucket of the widest
    chunk, and ``rows = step_rows(q_width, max_batch)``.
    ``prefill_waiting`` counts the chunks the row budget held back.
    ``take`` is ``[rows]`` too: for a row whose token the step planned
    before this one sampled and the host has not read yet, the lane of
    THAT step it comes from, else -1 (``tok`` holds)."""

    __slots__ = ("seqs", "slots_map", "tok", "pos", "page_ids", "slots",
                 "take", "kv_lens", "q_lens", "tables", "temps", "q_width",
                 "rows",
                 "n_prefill", "n_decode", "fed_prefill", "fed_decode",
                 "prefill_waiting", "bisect_group")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _Prestage:
    """The double-buffered plan half-step: admission work computed
    WHILE the device runs a fused window, against the projected
    post-window state (every live lane + ``window`` decode tokens, no
    finishes, no page churn).  ``matches`` decides at the next
    boundary whether the projection held — a finish, an eviction or a
    queue-head change invalidates it and the staged work is
    discarded."""

    __slots__ = ("running_ids", "head_id", "free_pages", "queue_depth",
                 "prediction")

    def __init__(self, running_ids, head_id, free_pages, queue_depth,
                 prediction):
        self.running_ids = running_ids
        self.head_id = head_id
        self.free_pages = free_pages
        self.queue_depth = queue_depth
        self.prediction = prediction   # (req_id, predicted_cost_s)

    def matches(self, sched: "Scheduler") -> bool:
        if tuple(s.req.id for s in sched.running) != self.running_ids:
            return False
        if not sched.waiting or sched.waiting[0].req.id != self.head_id:
            return False
        return sched.pool.available() == self.free_pages


class Scheduler:
    """Plans one ragged step per call; owns admission, page
    accounting, eviction and completion.  Thread-compatible: the
    engine serializes calls under its own lock."""

    def __init__(self, pool: PagePool, max_batch: int,
                 max_pages_per_seq: int, prefix_cache=None,
                 max_queue: int = 1024, max_prefill_chunk: int = 0,
                 max_seq_len: int = 0, perf_model=None,
                 max_step_cost_s: float = 0.0, ring_pages: int = 0,
                 lane_tables=None):
        self.pool = pool
        self.max_batch = int(max_batch)
        self.ppseq = int(max_pages_per_seq)
        # window and state layers (models.generation.CacheDescription):
        # every running sequence owns one of max_batch slots — a ring of
        # ring_pages pages in the window layers' pools, a row of the
        # state layers' arrays — and a plan's ``tables`` carry what the
        # slot holds behind the sequence's PagePool pages, laid out by
        # ``lane_tables(tables, slots, ring_pages)``, the step's own
        # ``CacheDescription.tables``.  Slots are outside PagePool: they
        # never run out (one a batch lane) and what they hold means
        # nothing once their sequence leaves (the step zeroes a state
        # when a sequence starts at position 0), so eviction and resume
        # re-prefill as for any model.  None: no such layer
        self.ring_pages = int(ring_pages)
        self.lane_tables = lane_tables
        self._free_slots = list(range(self.max_batch))[::-1]
        self.prefix_cache = prefix_cache
        self.max_queue = int(max_queue)
        # page capacity rounds UP to whole pages; the model's position
        # tables do not — admission must respect the tighter of the two
        # (out-of-range positions would silently clip in jnp.take)
        self.max_seq_len = int(max_seq_len)
        # 0: prefill a whole remaining prompt in one step; >0 caps the
        # per-iteration chunk (bounds Q and the step's latency impact
        # on co-scheduled decodes)
        self.max_prefill_chunk = int(max_prefill_chunk)
        # predicted-cost admission (tuning.learned): with a trained
        # batch_step head and a budget, new prefills are admitted only
        # while the PREDICTED next-step cost stays under the budget —
        # the cap follows what a prefill actually costs co-scheduled
        # decodes, not a raw page/token count
        self.perf_model = perf_model
        self.max_step_cost_s = float(max_step_cost_s or 0.0)
        self.deferred_admissions = 0
        self.waiting: deque = deque()
        self.running: List[_Sequence] = []
        self.evictions = 0
        # the packed layout's own account: rows the planned steps'
        # programs run, rows among them that carried no token, and
        # chunks the row budget made wait a step
        self.rows_planned = 0
        self.rows_empty = 0
        self.prefill_waits = 0
        # quarantine bisection (engine fault containment): while
        # non-empty, plan_step restricts each plan to the front
        # group's members (by request id) and pauses admission — the
        # engine splits a failed group in half and pushes both halves
        # here until the offender is isolated
        self.bisect_groups: deque = deque()
        # double-buffered plan (fused serving windows): admission
        # decisions pre-staged against the projected post-window state
        # while the device runs, committed or discarded at the boundary
        self._prestage: Optional[_Prestage] = None
        self._staged_pred = None
        self.prestaged_plans = 0
        self.prestage_commits = 0
        self.prestage_discards = 0

    # -- queue side ------------------------------------------------------
    def submit(self, req: Request) -> None:
        cap = self.ppseq * self.pool.page_size
        if self.max_seq_len:
            cap = min(cap, self.max_seq_len)
        if len(req.prompt) + req.max_new_tokens > cap:
            req._finish(error=f"request needs {len(req.prompt)} + "
                              f"{req.max_new_tokens} tokens; a sequence "
                              f"holds at most {cap}")
            return
        if len(self.waiting) >= self.max_queue:
            req._finish(error="queue full")
            return
        seq = _Sequence(req)
        if req.deadline_at is not None and self.perf_model is not None:
            # predicted-cost admission consults the remaining deadline:
            # a request whose full decode cannot fit inside it is doomed
            # — reject up front (HTTP maps error_kind="deadline" to
            # 503) instead of burning batch slots on a stream that must
            # be cancelled mid-flight.  The per-step prediction is a
            # conservative per-token estimate (it prices the admission
            # step, prefill included).
            pred = self._predicted_admit_cost(seq)
            if pred is not None:
                need_s = pred * max(req.max_new_tokens, 1)
                remaining = req.deadline_at - time.monotonic()
                if need_s > remaining:
                    req.error_kind = "deadline"
                    req._finish(
                        error=f"deadline infeasible: predicted "
                              f"{need_s:.3f}s of decode exceeds the "
                              f"{remaining:.3f}s remaining before the "
                              f"deadline")
                    return
        self.waiting.append(seq)

    def queue_depth(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        return bool(self.running or self.waiting)

    # -- page accounting -------------------------------------------------
    def _pages_needed(self, seq: _Sequence, new_len: int) -> int:
        ps = self.pool.page_size
        return max(0, -(-new_len // ps) - len(seq.pages))

    def _grow(self, seq: _Sequence, new_len: int) -> bool:
        """Allocate the pages ``seq`` needs to hold ``new_len`` tokens;
        False when the pool cannot satisfy it right now."""
        need = self._pages_needed(seq, new_len)
        if need == 0:
            return True
        if self.pool.available() < need and self.prefix_cache is not None:
            # reclaim cache-only pages (refcount 1, held by the cache
            # alone) before declaring exhaustion
            self.prefix_cache.reclaim(need - self.pool.available())
        if self.pool.available() < need:
            return False
        for _ in range(need):
            seq.pages.append(self.pool.alloc())
        return True

    def _release(self, seq: _Sequence) -> None:
        for page in seq.pages:
            self.pool.unref(page)
        seq.pages = []
        seq.shared = set()
        seq.kv_len = 0
        if seq.slot is not None:
            self._free_slots.append(seq.slot)
            seq.slot = None

    # -- predicted-cost admission ----------------------------------------
    def _next_chunk(self, seq: _Sequence, unread: Optional[StepPlan]):
        """``(start, tokens, lane)``: the tokens ``seq`` feeds next and
        the position of the first.  With ``unread`` (a step dispatched
        whose sampled tokens are still on the device) the sequence is
        seen as it will be once that step commits: its k/v grown by the
        step's rows, and, where the step sampled for it, one more token
        whose value the host does not know — fed as a placeholder with
        ``lane`` its lane in ``unread`` (-1 otherwise), unless the
        sample is, by count, the last of its budget."""
        start, tokens = seq.kv_len, seq.tokens
        if unread is not None:
            i = unread.slots_map.get(seq.req.id)
            if i is not None and unread.seqs[i] is seq:
                start = int(unread.kv_lens[i])
                if start >= len(tokens):
                    if seq.n_generated + 1 >= seq.req.max_new_tokens:
                        return start, [], -1
                    return start, [0], i
        end = start + self.max_prefill_chunk if self.max_prefill_chunk \
            else len(tokens)
        return start, tokens[start:end], -1

    def _chunk_len(self, seq: _Sequence) -> int:
        n = max(len(seq.req.prompt) + len(seq.req.tokens) - seq.kv_len,
                0)
        if self.max_prefill_chunk:
            n = min(n, self.max_prefill_chunk)
        return n

    def _predicted_admit_cost(self, seq: _Sequence,
                              projected_decode: bool = False,
                              unread: Optional[StepPlan] = None
                              ) -> Optional[float]:
        """The learned model's batch-step seconds for the NEXT
        iteration with ``seq`` admitted on top of the running batch
        (the same feature vector ``batch_step`` events log).  None when
        the model can't answer — admission then falls back to the raw
        caps; a model error must never wedge the queue.

        ``projected_decode`` evaluates the POST-window projection the
        fused path pre-stages against: every running lane a one-token
        decode (the state a full fused window leaves behind)."""
        chunk = self._chunk_len(seq)
        if projected_decode:
            chunks = [1 for _ in self.running]
            decode = len(self.running)
        else:
            chunks = [self._chunk_len(s) for s in self.running]
            decode = sum(1 for s in self.running
                         if s.kv_len >= len(s.req.prompt))
        feats = {
            "batch": float(len(self.running) + 1),
            "prefill_seqs": float(len(self.running) - decode + 1),
            "decode_seqs": float(decode),
            "q_width": float(max(chunks + [chunk, 1])),
            "tokens": float(sum(chunks) + chunk),
            "queue_depth": float(len(self.waiting)),
            "page_occupancy": round(
                1.0 - self.pool.available()
                / max(self.pool.num_pages - 1, 1), 4),
            # the step being priced is a single-step admission boundary
            "fused_steps": 1.0,
        }
        try:
            return self.perf_model.predict("batch_step", feats)
        except Exception:  # noqa: PTL401 — a perf-model failure must
            # never wedge admission; None falls back to the raw caps
            return None

    # -- admission / eviction --------------------------------------------
    def _admit_one(self, unread: Optional[StepPlan] = None
                   ) -> Optional[_Sequence]:
        if not self.waiting or len(self.running) >= self.max_batch:
            return None
        seq = self.waiting[0]
        if self.perf_model is not None and self.max_step_cost_s > 0:
            staged = self._staged_pred
            if staged is not None and staged[0] == seq.req.id:
                # double-buffered plan: the prediction was computed
                # while the device ran the last fused window
                pred = staged[1]
                self._staged_pred = None
            else:
                pred = self._predicted_admit_cost(seq, unread=unread)
            seq.predicted_cost_s = pred
            if pred is not None and pred > self.max_step_cost_s \
                    and self.running:
                # admitting this prefill would blow the step budget —
                # defer until the running batch shrinks.  An empty
                # batch always admits (the budget shapes latency, it
                # must never starve the queue)
                self.deferred_admissions += 1
                return None
        # refresh: an evicted requeued sequence re-enters with its
        # generated-so-far tokens included
        seq.tokens = list(seq.req.prompt) + list(seq.req.tokens)
        cached_pages: List[int] = []
        if self.prefix_cache is not None:
            cached_pages = self.prefix_cache.match(seq.req.prompt)
        ps = self.pool.page_size
        # always feed >= 1 token so the step produces logits; the
        # boundary token's rewrite into a shared page is value-
        # identical (same weights, same tokens, same positions)
        cached_len = min(len(cached_pages) * ps, len(seq.tokens) - 1)
        use_pages = cached_pages[:-(-cached_len // ps) if cached_len
                                 else 0]
        # NO free-list pre-check here: the pool may be held entirely by
        # cache-only prompt pages, and only ``_grow`` reclaims those.
        # Ref the matched pages FIRST so reclaim cannot free them out
        # from under us, then let _grow reclaim/allocate the rest; on
        # failure the shared refs roll back and the request stays at
        # the head of the queue.
        for page in use_pages:
            self.pool.ref(page)
            seq.pages.append(page)
            seq.shared.add(page)
        seq.kv_len = cached_len
        seq.cached_tokens = cached_len
        if not self._grow(seq, len(seq.tokens)):
            # pool short even after reclaiming cache-only pages
            self._release(seq)
            return None
        self.waiting.popleft()
        self.running.append(seq)
        if self.lane_tables is not None:
            seq.slot = self._free_slots.pop()
        return seq

    def _evict_victim(self, protect) -> Optional[_Sequence]:
        """Preempt the youngest running sequence (most recently
        admitted, none of ``protect``): free its pages, requeue it at
        the FRONT so it resumes as soon as pressure clears.  Sequences
        already laid into the current plan are protected — their pages
        are about to be written and must not be reallocated."""
        for seq in reversed(self.running):
            if seq in protect:
                continue
            self.running.remove(seq)
            self._release(seq)
            seq.req.evictions += 1
            self.evictions += 1
            self.waiting.appendleft(seq)
            return seq
        return None

    # -- completion (engine calls after each step) -----------------------
    def finish(self, seq: _Sequence, error: Optional[str] = None) -> None:
        """EOS / budget / failure: free the pages NOW — the next
        iteration's admission sees them."""
        if seq in self.running:
            self.running.remove(seq)
        self._release(seq)
        seq.req._finish(error=error)

    def drop(self, req: Request, error: str) -> bool:
        """Cancellation path: remove ``req`` wherever it sits (wait
        queue or running batch), free its pages NOW, and finish it with
        ``error``.  Returns True when it was still scheduled."""
        for seq in list(self.waiting):
            if seq.req is req:
                self.waiting.remove(seq)
                seq.req._finish(error=error)
                return True
        for seq in list(self.running):
            if seq.req is req:
                self.running.remove(seq)
                self._release(seq)
                seq.req._finish(error=error)
                return True
        req._finish(error=error)
        return False

    def rebind_pool(self, pool: PagePool, prefix_cache=None) -> None:
        """Watchdog relaunch: the abandoned dispatch may still write
        into the old device buffers, so the engine replaces them AND
        the host page accounting wholesale — rebind to the fresh pool,
        drop staged plans and any in-flight bisection episode."""
        self.pool = pool
        self.prefix_cache = prefix_cache
        self._prestage = None
        self._staged_pred = None
        self.bisect_groups.clear()
        # the window and state layers' arrays are fresh too: nobody
        # holds a slot
        self._free_slots = list(range(self.max_batch))[::-1]
        for seq in self.waiting:
            seq.slot = None

    # -- quarantine bisection (engine fault containment) -----------------
    def bisect_push_front(self, groups) -> None:
        """Push request-id groups at the FRONT of the bisection queue
        (the engine splits a failed batch in half and narrows first)."""
        for g in reversed(list(groups)):
            self.bisect_groups.appendleft(frozenset(g))

    def bisect_done(self, group) -> None:
        """A restricted plan for ``group`` resolved (ran clean, or was
        contained) — retire it."""
        if self.bisect_groups and self.bisect_groups[0] == group:
            self.bisect_groups.popleft()

    # -- the per-iteration plan ------------------------------------------
    def plan_step(self, unread: Optional[StepPlan] = None):
        """Admit what fits, grow pages for this iteration's tokens
        (evicting under pressure), and lay the step's tokens out as
        packed rows.  Returns (plan, admitted, evicted) — plan is None
        when nothing is runnable.

        **The row budget.**  A step's program is keyed by the bucket of
        its widest chunk and runs ``step_rows`` of that bucket.  Lanes
        that feed one token always go.  Wider chunks are taken in
        running order: the first always goes; a later one joins only if
        the step's tokens, with it, still fit the rows of the bucket of
        the widest chunk taken, and otherwise waits WHOLE for the next
        step — its lane is held and feeds nothing.  A chunk is never
        cut to fit: a remainder would have a width that no earlier step
        of the deployment compiled.  The first wide chunk in running
        order is the oldest one, so no sequence waits for ever.

        **One step ahead.**  ``unread`` is a plan that was dispatched
        and whose sampled tokens the host has not read: this plan is
        the one that follows it, made before it commits
        (:meth:`_next_chunk`).  A lane that sampled there feeds that
        token from the device (``take``); one whose budget that sample
        ends feeds nothing and keeps its lane and pages until the
        commit, so admission sees them one step later than a plan made
        after the commit would.  A lane with an ``eos_token_id`` is fed
        as if the unread token were not the end: if it was, this plan's
        row for it wrote one k/v slot past the sequence's end, in a page
        the sequence held when the row was planned, and the engine
        drops the row's token.  (The device runs its programs in
        dispatch order: where the commit frees that page and the next
        plan gives it to another sequence, the dropped row's write
        lands before the new owner's — as it already does when a
        request is cancelled between a dispatch and its read.)  Such a
        plan never evicts: where a
        sequence cannot grow it returns no plan, and the caller commits
        ``unread`` and plans again."""
        pre, self._prestage = self._prestage, None
        self._staged_pred = None
        if pre is not None:
            if pre.matches(self):
                self.prestage_commits += 1
                self._staged_pred = pre.prediction
            else:
                self.prestage_discards += 1
        # quarantine bisection: restrict the plan to the front group's
        # members and pause admission until the episode resolves
        group = None
        while self.bisect_groups:
            g = self.bisect_groups[0]
            if any(s.req.id in g for s in self.running):
                group = g
                break
            self.bisect_groups.popleft()   # members finished meanwhile
        admitted: List[_Sequence] = []
        evicted: List[_Sequence] = []
        if group is None:
            while True:
                seq = self._admit_one(unread)
                if seq is None:
                    break
                admitted.append(seq)

        # per-sequence chunk of NEW tokens this iteration: (seq, first
        # position, tokens, lane of ``unread`` the token is still in)
        active: List[Tuple[_Sequence, int, List[int], int]] = []
        runnable = [(seq, *self._next_chunk(seq, unread))
                    for seq in self.running
                    if group is None or seq.req.id in group]
        # (parked while the bisection probes otherwise)
        singles = sum(1 for _, _, chunk, _ in runnable if len(chunk) == 1)
        widest = wide_tokens = waiting = 0
        for seq, start, chunk, lane in runnable:
            if seq not in self.running:
                continue       # evicted by an earlier seq's growth
            if not chunk:
                continue
            if widest and len(chunk) > 1 and \
                    wide_tokens + len(chunk) + singles > step_rows(
                        _bucket(max(widest, len(chunk))), self.max_batch):
                waiting += 1
                continue       # over the row budget: next step, whole
            while not self._grow(seq, start + len(chunk)):
                if unread is not None:
                    # an eviction would requeue a sequence whose last
                    # token is still on the device: commit first
                    return None, admitted, evicted
                victim = self._evict_victim(
                    {seq} | {s[0] for s in active})
                if victim is None:
                    break
                evicted.append(victim)
                if victim in admitted:
                    admitted.remove(victim)
            if self._pages_needed(seq, start + len(chunk)) > 0:
                # could not grow even after evicting everything else;
                # park this sequence too and try again next iteration
                if seq in self.running:
                    self.running.remove(seq)
                    self._release(seq)
                    seq.req.evictions += 1
                    self.evictions += 1
                    self.waiting.appendleft(seq)
                    evicted.append(seq)
                continue
            active.append((seq, start, chunk, lane))
            if len(chunk) > 1:
                widest = max(widest, len(chunk))
                wide_tokens += len(chunk)

        if not active:
            return None, admitted, evicted

        b = self.max_batch
        qw = _bucket(max(len(chunk) for _, _, chunk, _ in active))
        n_rows = step_rows(qw, b)
        ps = self.pool.page_size
        tok = np.zeros((n_rows,), "int64")
        pos = np.zeros((n_rows,), "int32")
        page_ids = np.full((n_rows,), self.pool.sink, "int32")
        slots = np.zeros((n_rows,), "int32")
        take = np.full((n_rows,), -1, "int32")
        kv_lens = np.zeros((b,), "int32")
        q_lens = np.zeros((b,), "int32")
        tables = np.zeros((b, self.ppseq), "int32")
        lane_slots = np.zeros((b,), "int32")
        temps = np.zeros((b,), "float32")
        n_prefill = n_decode = 0
        fed_prefill = fed_decode = 0
        row = 0
        for i, (seq, start, chunk, lane) in enumerate(active):
            n = len(chunk)
            tables[i, :len(seq.pages)] = seq.pages
            if n == 1:                  # a decoding lane: no array work
                tok[row], pos[row], take[row] = chunk[0], start, lane
                page_ids[row] = seq.pages[start // ps]
                slots[row] = start % ps
            else:
                p = np.arange(start, start + n, dtype="int32")
                tok[row:row + n] = chunk
                pos[row:row + n] = p
                page_ids[row:row + n] = tables[i, p // ps]
                slots[row:row + n] = p % ps
            row += n
            kv_lens[i] = start + n
            q_lens[i] = n
            if self.lane_tables is not None:
                lane_slots[i] = seq.slot
            temps[i] = seq.req.temperature
            if start < len(seq.req.prompt):     # still eating prompt
                n_prefill += 1
                fed_prefill += n
            else:
                n_decode += 1
                fed_decode += n
        if self.lane_tables is not None:
            tables = self.lane_tables(tables, lane_slots, self.ring_pages)
        self.rows_planned += n_rows
        self.rows_empty += n_rows - row
        self.prefill_waits += waiting
        plan = StepPlan(seqs=[s[0] for s in active],
                        slots_map={s[0].req.id: i
                                   for i, s in enumerate(active)},
                        tok=tok, pos=pos, page_ids=page_ids,
                        slots=slots, take=take, kv_lens=kv_lens,
                        q_lens=q_lens,
                        tables=tables, temps=temps, q_width=qw,
                        rows=n_rows,
                        n_prefill=n_prefill, n_decode=n_decode,
                        fed_prefill=fed_prefill, fed_decode=fed_decode,
                        prefill_waiting=waiting, bisect_group=group)
        return plan, admitted, evicted

    def commit(self, plan: StepPlan) -> None:
        """Mark the plan's tokens as committed to the pages (called
        after the step ran).  Sequences whose request finished while
        the step was in flight (``stop()``, a failed step) have been
        released — their pages may already be reallocated, so nothing
        is committed for them."""
        for i, seq in enumerate(plan.seqs):
            if seq.req.done:
                continue
            seq.kv_len = int(plan.kv_lens[i])

    # -- fused serving windows (persistent-program step) -----------------
    def window_budget(self, plan: StepPlan, max_steps: int):
        """How many iterations the device may run on ``plan`` without
        a host boundary: clamp ``max_steps`` to the tightest remaining
        token budget (a lane hitting its budget finishes — the window
        exits there anyway) and to what the page pool can host WITHOUT
        eviction, then pre-allocate every page the window can touch
        and refresh ``plan.tables`` so the compiled loop's on-device
        append cursors stay in-bounds.  Returns ``(w, clamp_reason)``;
        ``w == 1`` means the single-step path (with its eviction
        machinery) should run instead — nothing was allocated."""
        w = int(max_steps)
        reason = "window_full"
        rem = min(seq.req.max_new_tokens - len(seq.req.tokens)
                  for seq in plan.seqs)
        w = min(w, max(rem, 1))
        avail = self.pool.available()
        while w > 1 and sum(self._pages_needed(s, s.kv_len + w)
                            for s in plan.seqs) > avail:
            w -= 1
            reason = "page_limit"
        if w <= 1:
            return 1, reason
        for seq in plan.seqs:
            if not self._grow(seq, seq.kv_len + w):
                # the avail math above makes this unreachable; any
                # pages already granted are owned and trimmed at the
                # next commit, so bailing to single-step is safe
                return 1, "page_limit"
        for i, seq in enumerate(plan.seqs):
            plan.tables[i, :len(seq.pages)] = seq.pages
        return w, reason

    def commit_window(self, plan: StepPlan, steps: int) -> None:
        """Commit a fused window's outcome: every surviving lane ran
        exactly ``steps`` decode iterations (the loop exits on the
        FIRST finish, so lanes never diverge mid-window).  Pages the
        clamped window reserved but never wrote are returned to the
        pool."""
        # plan_step counted the window's first iteration
        self.rows_planned += (int(steps) - 1) * plan.rows
        self.rows_empty += (int(steps) - 1) * (plan.rows - len(plan.seqs))
        for seq in plan.seqs:
            if seq.req.done:
                continue
            seq.kv_len += int(steps)
            self._trim_pages(seq)

    def _trim_pages(self, seq: _Sequence) -> None:
        """Drop owned pages past what ``kv_len`` occupies (window
        over-allocation after an early exit).  Trailing pages are
        never prefix-cache-shared — shared pages cover only the prompt
        prefix — so a plain unref is enough."""
        keep = -(-seq.kv_len // self.pool.page_size)
        while len(seq.pages) > max(keep, 1):
            self.pool.unref(seq.pages.pop())

    def prestage_plan(self, plan: StepPlan, window: int) -> None:
        """Double-buffered plan: called right after a fused window is
        DISPATCHED (device busy, host free) — run the expensive
        admission work for the next boundary against the projected
        post-window state: all plan lanes decoding, window pages
        already charged to the pool, queue unchanged.  ``plan_step``
        commits the staged work when the window exits exactly as
        projected (full run, no finishes) and discards it otherwise."""
        if not self.waiting:
            self._prestage = None
            return
        self.prestaged_plans += 1
        head = self.waiting[0]
        prediction = None
        if self.perf_model is not None and self.max_step_cost_s > 0:
            pred = self._predicted_admit_cost(head,
                                              projected_decode=True)
            prediction = (head.req.id, pred)
        self._prestage = _Prestage(
            running_ids=tuple(s.req.id for s in self.running),
            head_id=head.req.id,
            free_pages=self.pool.available(),
            queue_depth=len(self.waiting),
            prediction=prediction)
