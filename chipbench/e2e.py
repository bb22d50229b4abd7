"""End-to-end metrics, from the host clock, over the measured window.

Every time is taken from when a request was due (its scheduled arrival),
so a stall counts against the requests it delays; a rate is taken over
all the work and all the time of the window, and a tail over all the
requests due in it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class Readings:
    """What metric code reads: the entry's result, the run context, the
    chip's peaks and, in a traced run, the reduced trace."""

    cell: object
    result: dict
    ctx: object
    t_start: float
    peaks: dict
    reduced: Optional[object] = None

    @property
    def window(self):
        return self.result["window"]

    @property
    def recorder(self):
        return self.result["recorder"]

    @property
    def sizes(self):
        return self.result["sizes"]


def setup_s(r: Readings) -> float:
    """Process start to the window's opening: imports, weights, engine
    construction, router warm-start, warm-up compiles or cache loads and
    the pre-roll."""
    return r.window[0] - r.t_start


def query_mean_s(r: Readings) -> Optional[float]:
    qs = r.result.get("queries_due")
    if not qs:
        return None
    return float(np.mean([q.queue_wait + q.latency for q in qs]))


def itl_p99_ms(r: Readings) -> Optional[float]:
    g = r.recorder.token_gaps(*r.window)
    return 1000.0 * float(np.percentile(g, 99)) if g else None


def tokens_per_s(r: Readings) -> Optional[float]:
    """Output tokens that became visible inside the window, every
    request's, over the window's length."""
    lo, hi = r.window
    n = r.recorder.tokens_in(lo, hi)
    return n / (hi - lo) if n else None


def gap_quantiles(r: Readings) -> dict:
    """Token gaps of the window at a few quantiles, in ms, for the log."""
    g = np.asarray(r.recorder.token_gaps(*r.window), np.float64)
    if g.size == 0:
        return {}
    return {f"p{q}": round(1000.0 * float(np.percentile(g, q)), 3)
            for q in (50, 90, 95, 99)} | {"n": int(g.size)}


METRICS = {f.__name__: f for f in (setup_s, query_mean_s, itl_p99_ms,
                                   tokens_per_s)}


def compute(name: str, r: Readings) -> Optional[float]:
    return METRICS[name](r)
