"""Host-side observation of the system under test, from the benchmark's
own files: spans around the calls into each layer, and the time at which
each output token became visible.

``Recorder.wrap_engine`` replaces an engine instance's ``step`` and
``submit`` with wrappers (the class is untouched). Each engine keeps its
own log, written only by the thread that runs that engine's ``step``, so
pool replicas stepping on worker threads share no mutable state here.
Spans are ``jax.profiler.TraceAnnotation``s named ``bench.<name>``; they
cost about a microsecond when no trace is being taken.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import jax


def span(name: str):
    return jax.profiler.TraceAnnotation("bench." + name)


@dataclass
class Tracked:
    """One engine request as the client sees it."""

    req: object
    times: List[float] = field(default_factory=list)   # per output token
    seen: int = 0


@dataclass
class StepRecord:
    t0: float
    t1: float


class EngineLog:
    def __init__(self, name: str, engine):
        self.name = name
        self.engine = engine
        self.tracked: List[Tracked] = []
        self.live: List[Tracked] = []
        self.steps: List[StepRecord] = []

    def observe(self, t0: float, t1: float) -> None:
        """After a step that ran from ``t0`` to ``t1``: every token it
        produced became visible at ``t1``."""
        keep = []
        for tr in self.live:
            n = len(tr.req.output_ids)
            if n > tr.seen:
                tr.times.extend([t1] * (n - tr.seen))
                tr.seen = n
            if not tr.req.done:
                keep.append(tr)
        self.live = keep
        self.steps.append(StepRecord(t0, t1))

    def track(self, req) -> Tracked:
        tr = Tracked(req)
        self.tracked.append(tr)
        self.live.append(tr)
        return tr


class Recorder:
    def __init__(self):
        self.logs: List[EngineLog] = []

    def wrap_engine(self, engine, name: str) -> EngineLog:
        """Wrap ``engine.step`` and ``engine.submit`` of one instance."""
        log = EngineLog(name, engine)
        step, submit = engine.step, engine.submit

        def timed_step():
            with span("engine_step"):
                t0 = time.perf_counter()
                out = step()
                t1 = time.perf_counter()
            log.observe(t0, t1)
            return out

        def tracked_submit(*a, **kw):
            req = submit(*a, **kw)
            log.track(req)
            return req

        engine.step = timed_step
        engine.submit = tracked_submit
        self.logs.append(log)
        return log

    def wrap_method(self, obj, attr: str, name: str) -> None:
        """Put a span around one bound method of one instance."""
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            with span(name):
                return fn(*a, **kw)

        setattr(obj, attr, wrapped)

    def release(self) -> None:
        """Drop every reference to the engines, so that their weights and
        caches can be freed; the observations stay."""
        for log in self.logs:
            log.engine = None
            log.live = []
            for tr in log.tracked:
                if hasattr(tr.req, "_engine"):
                    tr.req._engine = None

    # ---- readings over the measured window -----------------------------
    def tracked(self):
        for log in self.logs:
            yield from log.tracked

    def steps_in(self, lo: float, hi: float):
        for log in self.logs:
            for s in log.steps:
                if lo <= s.t1 <= hi:
                    yield log, s

    def token_gaps(self, lo: float, hi: float) -> List[float]:
        """Every gap between consecutive output tokens of one request whose
        later token became visible inside [lo, hi]."""
        out = []
        for tr in self.tracked():
            ts = tr.times
            for a, b in zip(ts, ts[1:]):
                if lo <= b <= hi:
                    out.append(b - a)
        return out

    def tokens_in(self, lo: float, hi: float) -> int:
        return sum(1 for tr in self.tracked() for t in tr.times
                   if lo <= t <= hi)
