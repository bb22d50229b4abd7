"""Host phases of one traced run, from the program's own spans.

    python3 -m chipbench.phases --workload <name> --seeds 1,2 --seconds <s>

The program opens ``hf.*`` spans (``repro.tracing``) in its scheduler,
pool and engines; the benchmark opens ``bench.*`` spans around its calls
(``recorder.py``). Both land in the profiler trace's host plane, one line
per thread, on the device trace's clock. In one process, for each seed,
this runs the cell once with its trace, as ``--trace 1`` does, keeps the
trace and every engine's numeric ``stats`` (summed) when the window opens
and closes, and prints one JSON line with:

* ``idle_by_phase``: device idle seconds by the innermost span, program
  or benchmark, around each gap's midpoint (``no_span`` where none is);
  program spans keep their names, benchmark spans lose ``bench.``;
* ``self_s``: host seconds of each span name inside the window, less the
  spans nested in it on its thread;
* ``sched_host_ms``: mean ``hf.sched.pass`` less the parts of it covered
  on its thread by ``hf.engine.step``, ``hf.pool.step`` and
  ``hf.sched.nap``: the scheduler's own host work per pump pass;
* ``step_host_ms``: mean ``hf.engine.step`` less its ``hf.engine.sync``
  (the blocking device read), every engine and replica;
* ``padded_token_share``: share of the decode rows and prefill positions
  computed in the window that carried no token;
* ``unattributed_idle_share``: share of the window idle with no span;
* the cell's end-to-end and per-layer metrics, read as a run reads them
  (end-to-end ones under the trace, for its overhead).

Benchmark runs never call it: ``run.py`` and ``trace_reduce.py`` reduce
only the benchmark's own spans.
"""
from __future__ import annotations

import argparse
import bisect
import importlib
import json
import sys
import tempfile
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional

from chipbench import e2e, registry
from chipbench import run as R
from chipbench import trace_reduce as T

PREFIXES = ("hf.", T.SPAN_PREFIX)
SCHED_OUTSIDE = ("hf.engine.step", "hf.pool.step", "hf.sched.nap")


class Span(NamedTuple):
    name: str
    start: float      # ns, trace clock
    end: float
    thread: tuple     # (host plane, line index)


def host_spans(planes) -> List[Span]:
    """Every ``hf.*`` and ``bench.*`` event of the host planes."""
    out = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for i, line in enumerate(pl.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    s = float(e.start_ns)
                    out.append(Span(e.name, s, s + float(e.duration_ns),
                                    (pl.name, i)))
    return out


def window(spans: List[Span]) -> Optional[tuple]:
    w = [s for s in spans if s.name == T.WINDOW_SPAN]
    if not w:
        return None
    return min(s.start for s in w), max(s.end for s in w)


def device_gaps(planes, lo: float, hi: float) -> Optional[List[tuple]]:
    """Idle intervals of the first device inside [lo, hi], its ops read
    as ``trace_reduce`` reads them; None without a device plane."""
    for pl in planes:
        if pl.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in pl.lines}
            op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
            ivs = [] if op_line is None else [
                (float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
                for e in op_line.events]
            return T.gaps(T.clip(T.union(ivs), lo, hi), lo, hi)
    return None


def _label(name: str) -> str:
    return name[len(T.SPAN_PREFIX):] if name.startswith(T.SPAN_PREFIX) \
        else name


def idle_by_phase(gap_list: Iterable[tuple],
                  spans: List[Span]) -> Dict[str, float]:
    """Idle seconds by the innermost (shortest) span, on any thread, that
    holds each gap's midpoint. A sweep over the gaps in time order keeps
    only the spans open at the midpoint, so no enclosing span is missed."""
    flat = sorted((s.start, s.end, s.name) for s in spans
                  if s.name != T.WINDOW_SPAN)
    out: Dict[str, float] = defaultdict(float)
    i, live = 0, []
    for gs, ge in sorted(gap_list):
        mid = 0.5 * (gs + ge)
        while i < len(flat) and flat[i][0] <= mid:
            live.append(flat[i])
            i += 1
        live = [f for f in live if f[1] >= mid]
        best = min(live, key=lambda f: f[1] - f[0], default=None)
        out["no_span" if best is None else _label(best[2])] += \
            (ge - gs) / 1e9
    return dict(out)


def _covered(ivs: List[tuple], lo: float, hi: float) -> float:
    return sum(e - s for s, e in T.union(T.clip(ivs, lo, hi)))


def host_self_s(spans: List[Span], outer: str, inner: Iterable[str],
                lo: float, hi: float) -> List[float]:
    """For each ``outer`` span inside [lo, hi]: its length less the part
    covered by ``inner`` spans on its own thread, in seconds."""
    inner = set(inner)
    by_thread: Dict[tuple, List[tuple]] = defaultdict(list)
    for s in spans:
        if s.name in inner:
            by_thread[s.thread].append((s.start, s.end))
    for v in by_thread.values():
        v.sort()
    starts = {t: [iv[0] for iv in v] for t, v in by_thread.items()}
    out = []
    for s in spans:
        if s.name != outer or s.start < lo or s.end > hi:
            continue
        ivs = by_thread.get(s.thread, [])
        j = bisect.bisect_left(starts.get(s.thread, []), s.start)
        k = bisect.bisect_right(starts.get(s.thread, []), s.end)
        out.append((s.end - s.start - _covered(ivs[j:k], s.start, s.end))
                   / 1e9)
    return out


def self_by_name(spans: List[Span], lo: float,
                 hi: float) -> Dict[str, float]:
    """Host seconds of each span name inside [lo, hi], less the spans
    nested in it on its thread (spans on one thread nest)."""
    out: Dict[str, float] = defaultdict(float)
    by_thread: Dict[tuple, List[Span]] = defaultdict(list)
    for s in spans:
        if s.name != T.WINDOW_SPAN and s.start >= lo and s.end <= hi:
            by_thread[s.thread].append(s)
    for v in by_thread.values():
        v.sort(key=lambda s: (s.start, -s.end))
        stack: List[list] = []          # [span, seconds of its children]
        for s in v + [None]:
            while stack and (s is None or s.start >= stack[-1][0].end):
                top, kids = stack.pop()
                dur = (top.end - top.start) / 1e9
                out[_label(top.name)] += dur - kids
                if stack:
                    stack[-1][1] += dur
            if s is not None:
                stack.append([s, 0.0])
    return dict(out)


def mean_ms(xs: List[float]) -> Optional[float]:
    return 1000.0 * sum(xs) / len(xs) if xs else None


def padded_token_share(d: Dict[str, float]) -> Optional[float]:
    """From the window's change in the engines' summed counters."""
    keys = ("decode_rows", "tokens_out", "prefill_positions",
            "prefill_tokens")
    if not all(k in d for k in keys):
        return None
    computed = d["decode_rows"] + d["prefill_positions"]
    if computed <= 0:
        return None
    return 100.0 * (computed - d["tokens_out"] - d["prefill_tokens"]) \
        / computed


def reduce_phases(planes, stats_open: Dict[str, float],
                  stats_close: Dict[str, float], top: int = 12) -> dict:
    planes = list(planes)       # ProfileData's planes iterate only once
    spans = host_spans(planes)
    win = window(spans)
    if win is None:
        return {}
    lo, hi = win
    gap_list = device_gaps(planes, lo, hi)
    idle = idle_by_phase(gap_list, spans) if gap_list is not None else {}
    delta = {k: v - stats_open.get(k, 0) for k, v in stats_close.items()}
    passes = host_self_s(spans, "hf.sched.pass", SCHED_OUTSIDE, lo, hi)
    steps = host_self_s(spans, "hf.engine.step", ("hf.engine.sync",),
                        lo, hi)
    out = {"window_s": (hi - lo) / 1e9,
           "sched_host_ms": mean_ms(passes),
           "step_host_ms": mean_ms(steps),
           "padded_token_share": padded_token_share(delta),
           "idle_s": sum(idle.values()),
           "unattributed_idle_share": (
               100.0 * idle.get("no_span", 0.0) / ((hi - lo) / 1e9)
               if gap_list is not None else None),
           "idle_by_phase": sorted(([n, v] for n, v in idle.items()),
                                   key=lambda kv: -kv[1])[:top],
           "self_s": sorted(([n, v] for n, v in
                             self_by_name(spans, lo, hi).items()),
                            key=lambda kv: -kv[1])[:top],
           "passes": len(passes), "steps": len(steps)}
    return out


def stats_total(engines) -> Dict[str, float]:
    tot: Dict[str, float] = defaultdict(float)
    for e in engines:
        for k, v in e.stats.items():
            if isinstance(v, (int, float)):
                tot[k] += v
    return dict(tot)


class PhaseContext(R.RunContext):
    """A run context that also keeps the engines' summed ``stats`` each
    time the base class snapshots its compile counters: when the window
    opens, then when it closes."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.engine_stats: List[Dict[str, float]] = []

    def _compiles(self, engines):
        self.engine_stats.append(stats_total(engines))
        return R.RunContext._compiles(engines)


def run_phases(cell, seed: int, seconds: float, peaks: dict) -> dict:
    from jax.profiler import ProfileData
    entry = importlib.import_module(f"chipbench.drive_{cell.traffic['entry']}")
    with tempfile.TemporaryDirectory(prefix="chipbench-phases-") as tdir:
        ctx = PhaseContext(cell, seed, seconds, True, tdir)
        result = entry.run(ctx)
        planes = list(ProfileData.from_file(T.find_xplane(tdir)).planes)
        reduced = T.reduce_planes(planes)
        opened, closed = ctx.engine_stats
        phases = reduce_phases(planes, opened, closed)
    r = e2e.Readings(cell=cell, result=result, ctx=ctx, t_start=R.T_START,
                     peaks=peaks, reduced=reduced)
    metrics = {m.name: e2e.compute(m.name, r) for m in cell.end_to_end}
    metrics.update((m.name, m.reader(r)) for m in cell.per_layer)
    return {"workload": cell.name, "seed": seed,
            "correct": bool(ctx.check.ok), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "busy_s": reduced.busy_s, "phases": phases}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.phases")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu":
        R.fail(2, f"needs a TPU backend; JAX found {jax.default_backend()!r}")
    _, device = R.device_info()
    peaks = registry.peaks(device["kind"])
    sys.path.insert(0, str(registry.ROOT / "src"))
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    for s in (int(x) for x in args.seeds.split(",")):
        out = run_phases(cell, s, args.seconds, peaks)
        print(json.dumps(dict(out, device=device)), flush=True)


if __name__ == "__main__":
    main()
