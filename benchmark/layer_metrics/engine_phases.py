"""Readers of the serving loop's phase clock.

The engine's loop thread cuts its time into sibling phases
(``engine:plan``, ``engine:prepare``, ``engine:dispatch``,
``engine:host_read``, ``engine:commit`` and two waits) and writes each
step's seconds into its ``batch_step`` record: ``plan_s``,
``prepare_s``, ``dispatch_s``, ``read_s``, ``commit_s``; ``host_gap_s``,
from the end of the previous step's host read to the end of this step's
dispatch call with the waits for work taken out; and ``admit_queue_s``,
the queue wait of each request the step's plan admitted.

Each reader takes ``observed["batch_steps"]`` (the window's warm
``batch_step`` records, whole) and returns None where no record carries
its field, as the records of a program without the phase clock do.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark import harness


def _field(observed: Dict[str, Any], name: str, decode_only: bool
           ) -> List[float]:
    return [s[name] for s in observed.get("batch_steps") or ()
            if name in s and not (decode_only and s["prefill_seqs"] > 0)]


def _median_ms(values: List[float]) -> Optional[float]:
    return harness.median(values) * 1e3 if values else None


def host_gap_ms(observed: Dict[str, Any]) -> Optional[float]:
    """Median ``host_gap_s`` of decode-only steps: what the loop thread
    adds to a decode step while the device has nothing queued."""
    return _median_ms(_field(observed, "host_gap_s", True))


def dispatch_ms(observed: Dict[str, Any]) -> Optional[float]:
    """Median ``dispatch_s`` of decode-only steps: the jitted program's
    call until it returns (argument transfer and enqueue)."""
    return _median_ms(_field(observed, "dispatch_s", True))


def sched_plan_ms(observed: Dict[str, Any]) -> Optional[float]:
    """Median ``plan_s`` over all warm steps: the wait for the engine's
    lock, the deadline sweep, ``plan_step()`` and the admission
    bookkeeping."""
    return _median_ms(_field(observed, "plan_s", False))


def queue_wait_p90_ms(observed: Dict[str, Any]) -> Optional[float]:
    """90th percentile over admitted requests of submit-to-admission
    seconds (every ``admit_queue_s`` sample of the window)."""
    waits = [q for qs in _field(observed, "admit_queue_s", False)
             for q in qs]
    if not waits:
        return None
    p90_ms = harness.percentile(waits, 90.0) * 1e3
    print(f"trace: queue wait over {len(waits)} admitted requests: median "
          f"{harness.median(waits) * 1e3:.1f} ms, p90 {p90_ms:.1f} ms",
          flush=True)
    return p90_ms
