"""Run one benchmark cell once on the accelerator and print one JSON line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. With ``--trace 0`` the line carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace is taken
over part of the window and the line carries the per-layer metrics, the
device's busy time and a breakdown. The run refuses any backend but
``tpu`` and exits non-zero, printing no result, where the chip or the
program is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

from chipbench import registry  # noqa: E402

ROOT = registry.ROOT


def fail(code: int, msg: str):
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


class RunContext:
    """What an entry gets: the cell, the seed, the window's bookkeeping,
    the trace and the correctness check."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 trace_dir: str):
        from chipbench.drive_common import Check
        self.cell, self.seed, self.seconds, self.trace = \
            cell, seed, seconds, trace
        self.check = Check()
        self.controls: tuple = ()     # lower precisions to read as well
        self.memory_peak = 0
        self.marks = {}
        self.counters = {}
        self.trace_host = None
        self._trace_dir = trace_dir
        self._closed = False
        self._timer = None

    def note(self, msg: str) -> None:
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)

    def mark(self, name: str, **kw) -> None:
        self.marks[name] = dict(t=time.perf_counter() - T_START, **kw)
        self.note(f"{name} at {self.marks[name]['t']:.2f}s {kw or ''}")

    @staticmethod
    def _compiles(engines) -> "tuple[dict, int]":
        """Engines of one (config, max_len) share their step programs, so
        the count of each such set is the largest any of them reports."""
        groups, seeds = {}, 0
        for e in engines:
            k = (e.cfg, e.max_len)
            groups[k] = max(groups.get(k, 0),
                            int(e.stats.get("jit_retraces", 0)))
            seeds = max(seeds, int(e.stats.get("prefix_seed_compiles", 0)))
        return groups, seeds

    def open_window(self, w0: float, w1: float, engines) -> None:
        """Snapshot compile counters when the window opens, and take the
        trace inside it."""
        self.window = (w0, w1)
        spec = self.cell.cell.get("trace", {})
        off = float(spec.get("offset_s", 0.25 * self.seconds))
        length = min(float(spec.get("seconds", 4.0)),
                     max(self.seconds - off, 0.5))

        def body():
            time.sleep(max(0.0, w0 - time.perf_counter()))
            self.counters["open"] = self._compiles(engines)
            import jax
            # a program compiled inside the window is logged with its
            # shapes (warm-up is meant to leave none)
            jax.config.update("jax_log_compiles", True)
            if not self.trace:
                return
            time.sleep(max(0.0, w0 + off - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    lo = time.perf_counter()
                    time.sleep(length)
                    self.trace_host = (lo, time.perf_counter())
            finally:
                jax.profiler.stop_trace()

        self._timer = threading.Thread(target=body, name="chipbench-window",
                                       daemon=True)
        self._timer.start()

    def close_window(self, engines) -> None:
        if not self._closed:
            self._closed = True
            self.counters["close"] = self._compiles(engines)
            import jax
            jax.config.update("jax_log_compiles", False)

    def stop_trace(self) -> None:
        if self._timer is not None:
            self._timer.join()

    def compiles_in_window(self) -> int:
        (g0, s0), (g1, s1) = self.counters["open"], self.counters["close"]
        return sum(v - g0.get(k, 0) for k, v in g1.items()) + (s1 - s0)

    def watchdog(self, done: threading.Event, deadline: float) -> None:
        """End the process if the system is still inside one call at
        ``deadline``, past the drain limit: a hung device or host."""
        def body():
            if not done.wait(max(0.0, deadline - time.perf_counter())):
                print("chipbench: the system hung past the drain limit",
                      file=sys.stderr, flush=True)
                os._exit(4)
        threading.Thread(target=body, name="chipbench-watchdog",
                         daemon=True).start()


def device_info():
    import jax
    devs = jax.devices()
    return devs, {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = registry.load_cell(args.workload)
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(2, f"needs a TPU backend; JAX found {backend!r} "
                f"({jax.devices()[0].device_kind})")
    devs, device = device_info()
    if len(devs) < cell.chips:
        fail(2, f"cell {cell.name} needs {cell.chips} chips, JAX found "
                f"{len(devs)} ({device['kind']})")
    peaks = registry.peaks(device["kind"])
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        fail(3, f"the program is not in this checkout ({src})")
    sys.path.insert(0, str(src))
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    print(f"chipbench: {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} on {device} cache {cache_dir}",
          file=sys.stderr, flush=True)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   peaks)
    print(json.dumps(out), flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, controls: tuple = ()) -> dict:
    """Drive one run of ``cell`` on whatever backend JAX has and return
    the result line; the check lines go to standard error. ``controls``
    (lower precisions whose reading the limit-setting tool wants) are
    never asked for by a benchmark run."""
    import importlib
    from chipbench import e2e
    entry = importlib.import_module(f"chipbench.drive_{cell.traffic['entry']}")
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        ctx = RunContext(cell, seed, seconds, trace, tdir)
        ctx.controls = tuple(controls)
        result = entry.run(ctx)
        reduced = None
        if trace:
            from chipbench import trace_reduce
            reduced = trace_reduce.reduce_file(trace_reduce.find_xplane(tdir))
    readings = e2e.Readings(cell=cell, result=result, ctx=ctx,
                            t_start=T_START, peaks=peaks, reduced=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.reader(readings) if trace else e2e.compute(m.name, readings)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = dict(device, memory_peak_bytes=ctx.memory_peak)
    out = {"correct": bool(ctx.check.ok), "attempted": result["attempted"],
           "failed": result["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {"device_ops": reduced.top_ops(10),
                            "idle_gaps": reduced.top_idle(10)}
    info = {"setup": ctx.marks, "drain_s": result.get("drain_s"),
            "compiles_in_window": ctx.compiles_in_window(),
            "reference": result["reference"], "notes": ctx.check.notes,
            "detail": result.get("detail"),
            "token_gap_ms": e2e.gap_quantiles(readings)}
    print("chipbench: info " + json.dumps(info), file=sys.stderr)
    if controls:
        out["controls"] = result["reference"]["controls"]
    out["check"] = {k: {"value": v, "limit": lim}
                    for k, (v, lim) in ctx.check.numbers.items()}
    for k, (v, lim) in ctx.check.numbers.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    return out


if __name__ == "__main__":
    main()
