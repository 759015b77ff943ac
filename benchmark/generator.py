"""The one traffic generator: every mix and every training job is a data
file under ``benchmark/traffic/`` that this module reads.

Every seed gets the **same set** of sizes and arrival gaps, in another
order: lengths are the quantiles of the mix's distribution at
``(i + 0.5) / n`` and gaps the quantiles of the exponential, and the
seed only orders them (and draws the token ids).  Runs of different
seeds then do the same work, so their spread is the system's and not
the draw's.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_MASK = (1 << 32) - 1


def rng_for(seed: int, stream: int) -> np.random.RandomState:
    """A numpy generator for one stream of one run.  ``seed`` is any
    whole number (the driver's pass 2**31): it is folded to 32 bits."""
    s = int(seed)
    folded = (s ^ (s >> 32)) & _MASK
    return np.random.RandomState([folded, int(stream)])


def lengths(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths, ascending: the quantiles at ``(i + 0.5) / n`` of
    ``{"kind": "lognormal", "median", "sigma", "min", "max"}`` or of
    ``{"kind": "fixed", "value"}``, clipped to ``[min, max]``."""
    kind = dist["kind"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    norm = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * norm.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), int(dist["min"])),
                           int(dist["max"]))))
    return out


def gaps(n: int, total_s: float) -> List[float]:
    """``n`` inter-arrival gaps of a Poisson process, ascending: the
    exponential's quantiles at ``(i + 0.5) / n``, scaled so that they
    sum to ``total_s``.  Every request is then due inside the window
    and every seed offers exactly ``n``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = float(total_s) / sum(raw)
    return [g * scale for g in raw]


class Requests:
    """The mix's requests by index.  The ``n`` pairs of prompt and output
    length are the same for every seed (the two sets of quantiles, paired
    by one fixed shuffle); the seed only orders the pairs, and a run that
    sends more than ``n`` cycles them.  The prompt ids of request ``i``
    are drawn fresh from ``(seed, i)``, so no two requests share a prefix
    even when their lengths repeat."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int, n: int):
        outputs = lengths(mix["output"], n)
        np.random.RandomState(n).shuffle(outputs)       # the fixed pairing
        pairs = list(zip(lengths(mix["prompt"], n), outputs))
        rng_for(seed, 1).shuffle(pairs)                 # the run's order
        self.prompt_len = [p for p, _ in pairs]
        self.output_len = [o for _, o in pairs]
        self.vocab, self.seed, self.n = int(vocab), seed, n

    def get(self, i: int) -> Dict[str, Any]:
        ids = rng_for(self.seed, 1000 + i).randint(
            0, self.vocab, (self.prompt_len[i % self.n],))
        return {"prompt": ids.tolist(),
                "max_new_tokens": int(self.output_len[i % self.n])}


def due_times(rate: float, seconds: float, seed: int) -> List[float]:
    """Arrival times in ``[0, seconds)`` of an open loop at ``rate``
    requests a second: ``round(rate * seconds)`` Poisson gaps, shuffled
    by the seed."""
    n = max(1, int(round(float(rate) * float(seconds))))
    g = gaps(n, float(seconds))
    rng_for(seed, 2).shuffle(g)
    t, out = 0.0, []
    for gap in g:
        out.append(t)      # the first request is due at the window's
        t += gap           # start, the last one gap before its end
    return out


def prompt_buckets(mix: Dict[str, Any]) -> List[int]:
    """The powers of two that the mix's prompts round up to: the widths
    of the ragged step's programs this traffic can reach."""
    dist = mix["prompt"]
    lo, hi = (int(dist["value"]),) * 2 if dist["kind"] == "fixed" \
        else (int(dist["min"]), int(dist["max"]))
    out, b = [], 1
    while b < lo:
        b <<= 1
    while True:
        out.append(b)
        if b >= hi:
            return out
        b <<= 1


def train_batches(job: Dict[str, Any], vocab: int, seed: int):
    """``n_batches`` pairs of (ids, labels), each ``[batch, seq]`` int64
    random token ids from the seed."""
    rs = rng_for(seed, 3)
    shape = (int(job["batch"]), int(job["seq"]))
    return [(rs.randint(0, vocab, shape).astype(np.int64),
             rs.randint(0, vocab, shape).astype(np.int64))
            for _ in range(int(job["n_batches"]))]
