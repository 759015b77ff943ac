"""Reader of the serving loop's own counter of steps it ran ahead.

Since the engine dispatches step N+1 before it reads step N's tokens, a
``batch_step`` record carries ``ahead``: true where the step was
dispatched while the step before it was still unread on the device, so
that the device had it queued when that one ended; absent where nothing
was unread (the loop had read and committed first: a drain, the first
step after idling, a fused window).  ``steps_ahead_pct`` is the share of
the window's steps that ran ahead:

    100 x (records with ahead) / (records)

over ``observed["batch_steps"]``, the window's warm records.  It says
how often the host's commit, plan and dispatch ran under the device's
step instead of beside it.  Records of a program whose loop never runs
ahead carry no such field at all: where no record of the window carries
it the reader finds nothing to read, returns None, and the metric is
left out of the line — which is also what a window of drained steps
alone reads, and that is the cell to look at ``device_idle_pct`` in.

No metric file names this reader yet: ``steps_ahead_pct.batch`` and
``.longgen`` need two entries appended to ``BENCHMARK.json``'s
``per_layer``, and ``tests/benchmark_tests/test_step_rows_metric.py``
pins that list's last two names, so they wait for a ``benchmark`` PR
(PERF.md, Open questions).
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def steps_ahead_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = observed.get("batch_steps") or ()
    ahead = sum(1 for s in steps if s.get("ahead"))
    if not ahead:
        return None
    return 100.0 * ahead / len(steps)
