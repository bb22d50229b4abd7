"""The trace reducer: on hand-made planes, and on a trace recorded on a
TPU v5e (``testdata/engine_v5e.xplane.pb``)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace_reduce as T

DATA = Path(__file__).resolve().parents[1] / "testdata"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes():
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_decode_fn(123)", 100, 40),
                                       ev("jit_prefill_fn(9)", 200, 50),
                                       ev("jit_decode_fn(123)", 300, 40)]),
        NS(name="XLA Ops", events=[ev("%fusion.1 = f32[] fusion()", 100, 30),
                                   ev("%fusion.2 = f32[] fusion()", 120, 20),
                                   ev("%dot.3 = f32[] dot()", 200, 50),
                                   ev("%fusion.1 = f32[] fusion()", 300, 40)])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.trace_window", 50, 400),
        ev("bench.engine_step", 90, 170),
        ev("bench.arrival_wait", 260, 35),
        ev("other", 0, 1000)])])
    return [dev, host, NS(name="/host:metadata", lines=[])]


def test_busy_idle_programs_and_attribution():
    r = T.reduce_planes(planes())
    assert r.window_s == pytest.approx(400e-9)
    # ops cover [100,140) [200,250) [300,340): 130 ns of 400
    assert r.busy_s == pytest.approx(130e-9)
    assert r.program_time("jit_decode_fn") == (pytest.approx(80e-9), 2)
    assert r.program_time("jit_prefill_fn") == (pytest.approx(50e-9), 1)
    assert r.ops["jit_decode_fn/fusion.1"] == pytest.approx(70e-9)
    assert r.ops["jit_prefill_fn/dot.3"] == pytest.approx(50e-9)
    # gaps by midpoint: [50,100) at 75 before any bench span;
    # [140,200) at 170 in engine_step; [250,300) at 275 in arrival_wait
    # (the shorter of it and engine_step, which ends at 260 anyway);
    # [340,450) at 395 in none
    idle = r.idle_by_span
    assert idle["engine_step"] == pytest.approx(60e-9)
    assert idle["arrival_wait"] == pytest.approx(50e-9)
    assert idle["no_span"] == pytest.approx(160e-9)
    assert r.busy_s + sum(idle.values()) == pytest.approx(r.window_s)
    assert r.top_ops(1)[0][0] == "jit_decode_fn/fusion.1"


def test_union_and_gaps():
    assert T.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert T.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_recorded_tpu_trace():
    """A trace taken on a TPU v5e of a qwen2-1.5b engine (4 slots x 2048
    lines) serving two requests: four prefill and five decode programs."""
    r = T.reduce_file(str(DATA / "engine_v5e.xplane.pb"))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s < 1.0
    dec, n_dec = r.program_time("jit_decode_fn")
    pre, n_pre = r.program_time("jit_prefill_fn")
    assert (n_dec, n_pre) == (4, 3)
    assert 0.008 < dec / n_dec < 0.010        # ~9 ms per decode step
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert not any(n.split("/")[1].startswith("while")
                   for n, _ in r.top_ops(10))
