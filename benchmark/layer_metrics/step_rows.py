"""Reader of the packed step's own counter.

Since the serving step lays its token rows out flat, a ``batch_step``
record carries ``rows``: the packed rows its program ran the matmuls,
norms, rotary and k/v write over (a function of ``q_width`` and the
lanes alone, times ``fused_steps``), beside ``tokens``, the rows among
them that carried a token.  ``step_rows_empty_pct`` is the share of the
window's rows that carried none:

    100 x (1 - sum(tokens) / sum(rows))

over ``observed["batch_steps"]``, the window's warm records.  It says
how often the layout pays: a decode-only step is full, a prompt alone
in its step fills what its length leaves of the bucket's rows.  A
record without the field, as a program that pads every lane to the
widest chunk writes, reads as nothing: None, and the metric is left out
of the line (``padded_rows_pct`` is that program's measure).
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def step_rows_empty_pct(observed: Dict[str, Any]) -> Optional[float]:
    steps = [s for s in observed.get("batch_steps") or () if "rows" in s]
    rows = sum(int(s["rows"]) for s in steps)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(int(s["tokens"]) for s in steps) / rows)
