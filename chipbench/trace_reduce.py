"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

* busy time: the union of the intervals in which an XLA op ran on each
  device (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane), averaged
  over devices;
* per-program device time: the ``XLA Modules`` events, keyed by program
  name without its fingerprint (``jit_decode_fn``);
* device op time by op, each op named with the program it ran in (loop
  and call ops, whose events span their bodies' ops, are left out);
* idle gaps inside the traced window, each attributed to the innermost
  of the benchmark's host spans (``bench.*`` trace annotations) around
  the gap's midpoint.

Host and device events share the trace's clock. The window is the
``bench.trace_window`` host span when present, else the span of all
device events.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"

Interval = Tuple[float, float]   # (start_ns, end_ns)


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    programs: Dict[str, List[float]] = field(default_factory=dict)
    ops: Dict[str, float] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def program_time(self, prefix: str) -> Tuple[float, int]:
        """(total seconds, executions) of programs whose name starts
        with ``prefix``."""
        tot, n = 0.0, 0
        for name, durs in self.programs.items():
            if name.startswith(prefix):
                tot += sum(durs)
                n += len(durs)
        return tot, n

    def top_ops(self, k: int = 10) -> List[list]:
        return [[n, s] for n, s in sorted(self.ops.items(),
                                          key=lambda kv: -kv[1])[:k]]

    def top_idle(self, k: int = 10) -> List[list]:
        return [[n, s] for n, s in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:k]]


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


CONTAINERS = ("while", "conditional", "call")


def _op_name(raw: str) -> str:
    head = raw.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def _is_container(name: str) -> bool:
    """A loop or call op, whose event spans the ops of its body."""
    return name.split(".", 1)[0] in CONTAINERS


def _program_name(raw: str) -> str:
    return raw.split("(", 1)[0]


LOOKBACK = 64   # spans checked per gap, latest-starting first


def attribute(gap_list: List[Interval],
              spans: Dict[str, List[Interval]]) -> Dict[str, float]:
    """Idle seconds per host span name. A gap goes to the innermost
    (shortest) span that holds its midpoint, ``no_span`` where none does."""
    flat = sorted((s, e, n) for n, ivs in spans.items()
                  if n != WINDOW_SPAN for s, e in ivs)
    starts = [f[0] for f in flat]
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in gap_list:
        mid = 0.5 * (gs + ge)
        best, best_len = "no_span", float("inf")
        j = bisect.bisect_right(starts, mid)
        for s, e, n in reversed(flat[max(0, j - LOOKBACK):j]):
            if e >= mid and e - s < best_len:
                best, best_len = n[len(SPAN_PREFIX):], e - s
        out[best] += (ge - gs) / 1e9
    return dict(out)


def reduce_planes(planes) -> Reduced:
    """Reduce an iterable of planes (``ProfileData.planes`` or objects
    with ``name``, ``lines``; lines with ``name``, ``events``; events with
    ``name``, ``start_ns``, ``duration_ns``)."""
    dev_ops: List[List[Interval]] = []
    modules: List[Tuple[float, float, str]] = []
    ops: List[Tuple[float, float, str]] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for pl in planes:
        if pl.name.startswith("/device:TPU:"):
            lines = {l.name: l for l in pl.lines}
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            ivs = []
            if op_line is not None:
                for e in op_line.events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    ivs.append((s, s + d))
                    ops.append((s, s + d, _op_name(e.name)))
            dev_ops.append(union(ivs))
            if "XLA Modules" in lines:
                for e in lines["XLA Modules"].events:
                    s, d = float(e.start_ns), float(e.duration_ns)
                    modules.append((s, s + d, _program_name(e.name)))
        elif pl.name.startswith("/host:"):
            for l in pl.lines:
                for e in l.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        spans[e.name].append((s, s + float(e.duration_ns)))
    if WINDOW_SPAN in spans:
        lo = min(s for s, _ in spans[WINDOW_SPAN])
        hi = max(e for _, e in spans[WINDOW_SPAN])
    else:
        allv = [iv for d in dev_ops for iv in d]
        lo = min((s for s, _ in allv), default=0.0)
        hi = max((e for _, e in allv), default=0.0)
    window = max(hi - lo, 0.0)
    busy_per = [sum(e - s for s, e in clip(d, lo, hi)) for d in dev_ops]
    n_dev = len(dev_ops)
    busy = sum(busy_per) / n_dev if n_dev else 0.0
    programs: Dict[str, List[float]] = defaultdict(list)
    for s, e, n in modules:
        if s >= lo and e <= hi:
            programs[n].append((e - s) / 1e9)
    mod_sorted = sorted(modules)
    mod_starts = [m[0] for m in mod_sorted]
    op_time: Dict[str, float] = defaultdict(float)
    for s, e, n in ops:
        if e <= lo or s >= hi or _is_container(n):
            continue
        i = bisect.bisect_right(mod_starts, s) - 1
        prog = mod_sorted[i][2] if i >= 0 and mod_sorted[i][1] >= s else "?"
        op_time[f"{prog}/{n}"] += (min(e, hi) - max(s, lo)) / 1e9
    idle = {}
    if dev_ops:
        idle = attribute(gaps(clip(dev_ops[0], lo, hi), lo, hi), spans)
    return Reduced(window_s=window / 1e9, busy_s=busy / 1e9,
                   devices=n_dev, programs=dict(programs),
                   ops=dict(op_time), idle_by_span=idle)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
