"""Host phases from the program's spans (``chipbench.phases``): on
hand-made planes with two threads, against ``trace_reduce`` where a trace
holds no program span, the dispatch-wait reader on hand-made results, and
a whole traced run of a tiny fleet on the CPU."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import e2e, registry
from chipbench import phases as P
from chipbench import trace_reduce as T
from chipbench.tests import tiny

DATA = Path(__file__).resolve().parents[1] / "testdata"


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def planes():
    """Device ops [100,140) [200,250) [300,340) [445,460); the scheduler's
    thread runs two pump passes, the first stepping the edge engine, the
    second a pool pass whose replica steps on a worker thread."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%a = f32[] fusion()", 100, 140),
                                   ev("%b = f32[] fusion()", 200, 250),
                                   ev("%c = f32[] fusion()", 300, 340),
                                   ev("%d = f32[] fusion()", 445, 460)])])
    sched = NS(name="python", events=[
        ev("bench.trace_window", 50, 500),
        ev("hf.sched.pass", 60, 200), ev("bench.engine_step", 88, 192),
        ev("hf.engine.step", 90, 190), ev("hf.engine.sync", 150, 180),
        ev("hf.sched.pass", 200, 440), ev("hf.pool.step", 210, 300),
        ev("hf.sched.nap", 310, 330), ev("hf.sched.complete", 340, 360),
        ev("other", 0, 1000)])
    worker = NS(name="python", events=[
        ev("hf.engine.step", 220, 290), ev("hf.engine.sync", 260, 285)])
    return [dev, NS(name="/host:CPU", lines=[sched, worker]),
            NS(name="/host:metadata", lines=[])]


def test_idle_goes_to_the_innermost_span_on_any_thread():
    pl = planes()
    spans = P.host_spans(pl)
    assert P.window(spans) == (50.0, 500.0)
    idle = P.idle_by_phase(P.device_gaps(pl, 50.0, 500.0), spans)
    # gap mids: 75 in the first pass; 170 in the edge engine's sync;
    # 275 in the worker's sync (inside the pool pass); 392.5 in the
    # second pass; 480 in no span
    assert idle == pytest.approx({"hf.sched.pass": 155e-9,
                                  "hf.engine.sync": 110e-9,
                                  "no_span": 40e-9})
    red = T.reduce_planes(pl)
    assert red.busy_s + sum(idle.values()) == pytest.approx(red.window_s,
                                                            abs=1e-15)


def test_host_time_less_the_spans_nested_on_its_own_thread():
    spans = P.host_spans(planes())
    # pass 1: 140 less the engine step's 100; pass 2: 240 less the pool
    # step's 90 and the nap's 20 (the worker's step is on another thread)
    assert P.host_self_s(spans, "hf.sched.pass", P.SCHED_OUTSIDE, 50, 500) \
        == pytest.approx([40e-9, 130e-9])
    assert P.host_self_s(spans, "hf.engine.step", ("hf.engine.sync",), 50,
                         500) == pytest.approx([70e-9, 45e-9])
    assert P.self_by_name(spans, 50, 500) == pytest.approx({
        "hf.sched.pass": 146e-9, "engine_step": 4e-9,
        "hf.engine.step": 115e-9, "hf.engine.sync": 55e-9,
        "hf.pool.step": 90e-9, "hf.sched.nap": 20e-9,
        "hf.sched.complete": 20e-9})


def test_reduce_phases_reads_every_metric():
    opened = {"decode_rows": 10, "tokens_out": 5, "prefill_positions": 100,
              "prefill_tokens": 60, "steps": 3}
    closed = {"decode_rows": 30, "tokens_out": 20, "prefill_positions": 164,
              "prefill_tokens": 110, "steps": 9}
    # planes as ProfileData gives them: one pass of an iterator
    out = P.reduce_phases(iter(planes()), opened, closed)
    assert out["window_s"] == pytest.approx(450e-9)
    assert out["idle_s"] == pytest.approx(305e-9)    # window less busy
    assert out["sched_host_ms"] == pytest.approx(85e-6)
    assert out["step_host_ms"] == pytest.approx(57.5e-6)
    assert out["padded_token_share"] == pytest.approx(100 * 19 / 84)
    assert out["unattributed_idle_share"] == pytest.approx(100 * 40 / 450)
    assert out["idle_by_phase"][0] == ["hf.sched.pass", pytest.approx(155e-9)]
    assert (out["passes"], out["steps"]) == (2, 2)


def test_padded_share_needs_the_counters():
    assert P.padded_token_share({"tokens_out": 3}) is None
    assert P.padded_token_share(dict(decode_rows=0, tokens_out=0,
                                     prefill_positions=0,
                                     prefill_tokens=0)) is None
    assert P.padded_token_share(dict(decode_rows=8, tokens_out=6,
                                     prefill_positions=40,
                                     prefill_tokens=25)) == \
        pytest.approx(100 * 17 / 48)


def test_without_a_window_or_a_device_nothing_is_read():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("hf.sched.pass", 0, 10)])])
    assert P.reduce_phases([host], {}, {}) == {}
    host.lines[0].events.append(ev("bench.trace_window", 0, 20))
    out = P.reduce_phases([host], {}, {})
    assert out["unattributed_idle_share"] is None
    assert out["idle_by_phase"] == [] and out["sched_host_ms"] == \
        pytest.approx(10e-6)


def _agrees_with_trace_reduce(pl, lo, hi):
    red = T.reduce_planes(pl)
    idle = P.idle_by_phase(P.device_gaps(pl, lo, hi), P.host_spans(pl))
    assert idle.keys() == red.idle_by_span.keys()
    for k, v in red.idle_by_span.items():
        assert idle[k] == pytest.approx(v, rel=1e-9)


def test_bench_spans_alone_attribute_as_trace_reduce_does():
    pl = planes()
    sched = pl[1].lines[0]
    sched.events = [e for e in sched.events if not e.name.startswith("hf.")]
    pl[1].lines = [sched]
    _agrees_with_trace_reduce(pl, 50.0, 500.0)


def test_recorded_tpu_trace_attributes_as_trace_reduce_does():
    from jax.profiler import ProfileData
    pl = list(ProfileData.from_file(
        str(DATA / "engine_v5e.xplane.pb")).planes)
    dev = next(p for p in pl if p.name == "/device:TPU:0")
    ops = list(next(ln for ln in dev.lines if ln.name == "XLA Ops").events)
    lo = min(float(e.start_ns) for e in ops)
    hi = max(float(e.start_ns) + float(e.duration_ns) for e in ops)
    _agrees_with_trace_reduce(pl, lo, hi)


def _due(*waits):
    return [NS(results={i: NS(dispatch_wait=w) for i, w in enumerate(q)})
            for q in waits]


def test_dispatch_wait_reader():
    read = registry.load_reader("dispatch_wait_mean_s")

    def readings(qs):
        return e2e.Readings(cell=None, result={"queries_due": qs}, ctx=None,
                            t_start=0.0, peaks={})
    assert read(readings(_due([0.0, 2.0], [1.0]))) == pytest.approx(1.0)
    assert read(readings([])) is None
    # a program without the counter: nothing, and no error
    assert read(readings([NS(results={0: NS(latency=1.0)})])) is None


def test_traced_tiny_fleet_run_reads_every_phase(tmp_path):
    """The trace starts 1.5 s into the window, past the step programs
    that the CPU compiles on first use (a span open when the trace
    starts is not in it)."""
    from repro.serving import engine
    model = tiny.small_model("qwen2-1.5b")
    cell = dict(tiny.FLEET_CELL, trace={"offset_s": 1.5, "seconds": 1.5})
    tiny.write(tmp_path, [("tiny.fleet", "tiny-qwen2", model, "fmix",
                           tiny.FLEET_MIX, cell)],
               metrics=("dispatch_wait_mean_s",))
    cell = registry.load_cell("tiny.fleet", tmp_path, tmp_path / "chipbench")
    _, peaks = tiny.cpu_device()
    engine._jit_steps.cache_clear()
    try:
        out = P.run_phases(cell, 2**31 + 5, 4.0, peaks)
    finally:
        engine._jit_steps.cache_clear()
    assert out["correct"] is True
    m, ph = out["metrics"], out["phases"]
    assert m["itl_p99_ms"] > 0 and m["setup_s"] > 0
    assert m["dispatch_wait_mean_s"] >= 0
    assert ph["passes"] > 0 and ph["steps"] > 0
    assert 0 < ph["sched_host_ms"] and 0 < ph["step_host_ms"]
    assert 0 < ph["padded_token_share"] < 100
    # no device plane on the CPU: no idle to put down to a phase
    assert ph["unattributed_idle_share"] is None
    names = {n for n, _ in ph["self_s"]}
    assert {"hf.sched.pass", "hf.engine.step"} <= names
