"""Open-loop traffic from a mix file: one fixed trace per mix.

One general generator reads every ``traffic/<mix>.json``: arrival times
of a Poisson process at the file's fixed ``rate``, and the queries, in
equal shares from the file's benchmarks. The pre-roll and the measured
window are drawn apart, each with a fixed number of gaps (quantiles of
the exponential) and queries, in an order drawn from the mix's own
``trace_seed``, not from ``--seed``: a run replays the same trace on
every seed, and the seed draws the weights. Near the knee the order of
arrivals decides how long the queue grows, so an order per seed would
be a different load per seed.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

# independent random streams drawn from one seed
STREAM_ARRIVALS, STREAM_ORDER = 1, 4
PREROLL, WINDOW = 0, 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def poisson_stratified(rate: float, horizon: float,
                       rng: np.random.Generator) -> List[float]:
    """``round(rate * horizon)`` arrivals whose gaps are the exponential
    quantiles, shuffled by ``rng`` and scaled to end inside the horizon."""
    n = int(round(rate * horizon))
    if n <= 0:
        return []
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps = rng.permutation(gaps)
    t = np.cumsum(gaps)
    t *= min(1.0, horizon * (1.0 - 0.5 / n) / float(t[-1]))
    return [float(x) for x in t]


def counts(spec: dict, seconds: float) -> "tuple[int, int]":
    """Arrivals in the pre-roll and in the window."""
    rate = float(spec["rate"])
    return (int(round(rate * float(spec.get("preroll_s", 0.0)))),
            int(round(rate * float(seconds))))


def arrivals(spec: dict, seconds: float) -> List[float]:
    """Arrival times (seconds from the start of the schedule): the
    pre-roll's, then the window's, which all fall inside the window."""
    if spec.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {spec.get('arrival')!r}")
    rate, pre = float(spec["rate"]), float(spec.get("preroll_s", 0.0))
    out = []
    for part, (start, length) in ((PREROLL, (0.0, pre)),
                                  (WINDOW, (pre, float(seconds)))):
        rng = rng_for(spec["trace_seed"], STREAM_ARRIVALS + 10 * part)
        out += [start + t for t in poisson_stratified(rate, length, rng)]
    return out


def fleet_queries(spec: dict, seconds: float, gen_benchmark,
                  extra: int = 0) -> "tuple[list, list]":
    """The queries of the pre-roll and of the window, in arrival order,
    and ``extra`` more for warm-up. ``gen_benchmark`` is the program's
    seeded query generator (queries are the system's own input format).
    The mix's benchmarks are taken in turn from one pool drawn with its
    ``trace_seed``: the window's queries first, then the pre-roll's, then
    the warm-up's, so no two share an id."""
    n_pre, n_win = counts(spec, seconds)
    names = list(spec["benchmarks"])
    total = n_win + n_pre + extra
    per = math.ceil(total / len(names))
    seed = int(spec["trace_seed"])
    got = {b: gen_benchmark(b, per, seed=seed) for b in names}
    pool = [got[b][i] for i in range(per) for b in names][:total]
    win, pre = pool[:n_win], pool[n_win:n_win + n_pre]
    warm = pool[n_win + n_pre:]

    def shuffled(part, qs):
        order = rng_for(seed, STREAM_ORDER + 10 * part).permutation(len(qs))
        return [qs[int(i)] for i in order]

    return shuffled(PREROLL, pre) + shuffled(WINDOW, win), warm


def window_of(spec: dict, seconds: float) -> "tuple[float, float]":
    start = float(spec.get("preroll_s", 0.0))
    return start, start + float(seconds)


def digest(items) -> str:
    """A short stable digest of generated inputs, printed per run."""
    import hashlib
    h = hashlib.sha256()
    for x in items:
        h.update(repr(x).encode())
    return h.hexdigest()[:16]


def clip_prompt(ids: List[int], max_len: int, max_new: int,
                vocab: int) -> List[int]:
    """The context an engine serves for a prompt: ids clamped below the
    vocabulary, head-truncated to leave room for the answer (the engine's
    documented admission rule)."""
    ids = [min(int(i), vocab - 1) for i in ids]
    return ids[-(max_len - max_new - 1):]
