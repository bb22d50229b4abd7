"""End-to-end arithmetic and the per-layer readers, on hand-made
observations: times from the scheduled arrival, tails over every token
gap in the window."""
from __future__ import annotations

import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import e2e, registry
from chipbench.recorder import EngineLog, Recorder, Tracked


def _req(n_out, done=True):
    return SimpleNamespace(output_ids=list(range(n_out)), done=done,
                           prompt_ids=[1] * 8, max_new_tokens=4)


def _recorder(tracked, steps=()):
    rec = Recorder()
    log = EngineLog("engine", engine=None)
    log.tracked = list(tracked)
    log.steps = list(steps)
    rec.logs.append(log)
    return rec


def _readings(result, **kw):
    return e2e.Readings(cell=None, result=result, ctx=kw.get("ctx"),
                        t_start=kw.get("t_start", 0.0),
                        peaks={"flops_bf16": 100.0}, reduced=kw.get("red"))


def test_itl_takes_every_gap_ending_in_the_window():
    a = Tracked(_req(4), times=[9.0, 10.5, 10.7, 25.0])
    b = Tracked(_req(3), times=[11.0, 11.0, 11.4])
    rec = _recorder([a, b])
    r = _readings({"window": (10.0, 20.0), "recorder": rec})
    gaps = [1.5, 0.2, 0.0, 0.4]         # 9.0->10.5 ends inside; 25.0 not
    assert sorted(rec.token_gaps(10.0, 20.0)) == pytest.approx(sorted(gaps))
    assert e2e.compute("itl_p99_ms", r) == \
        pytest.approx(1000 * np.percentile(gaps, 99))


def test_query_mean_is_from_scheduled_arrival_to_final_result():
    qs = [SimpleNamespace(queue_wait=0.5, latency=2.0, api_cost=0.01),
          SimpleNamespace(queue_wait=0.0, latency=1.0, api_cost=0.03)]
    r = _readings({"window": (5.0, 15.0), "queries_due": qs})
    assert e2e.compute("query_mean_s", r) == pytest.approx(1.75)
    assert e2e.compute("setup_s", r) == pytest.approx(5.0)
    read = registry.load_reader("api_usd_per_query")
    assert read(r) == pytest.approx(0.02)
    assert registry.load_reader("queue_wait_mean_s")(r) == pytest.approx(0.25)


def test_step_observation_times_every_new_token_at_the_step_end():
    log = EngineLog("e", engine=None)
    r1, r2 = _req(0, done=False), _req(3, done=False)
    t1, t2 = log.track(r1), log.track(r2)
    t2.seen, t2.times = 3, [0.1, 0.2, 0.3]
    r1.output_ids = [7, 8]          # prefill finished and decoded: +2
    r2.output_ids = [0, 1, 2, 3]    # one decode token
    r2.done = True
    log.observe(1.0, 1.5)
    assert t1.times == [1.5, 1.5] and t2.times[-1] == 1.5
    assert log.live == [t1] and (log.steps[-1].t0, log.steps[-1].t1) == \
        (1.0, 1.5)
    log.observe(1.5, 1.6)           # nothing new: no time recorded
    assert t1.times == [1.5, 1.5]


def test_decode_device_ms_is_decode_program_time_per_execution():
    red = SimpleNamespace(program_time=lambda p: (0.5, 2)
                          if p == "jit_decode_fn" else (0.0, 0))
    r = _readings({"window": (0, 10), "recorder": _recorder([])}, red=red)
    assert registry.load_reader("decode_device_ms")(r) == \
        pytest.approx(250.0)


def test_readers_without_a_trace_return_nothing():
    r = _readings({"window": (0, 1), "recorder": _recorder([])})
    for name in ("decode_device_ms", "idle_share"):
        assert registry.load_reader(name)(r) is None


@pytest.mark.parametrize("name", ["decode_device_ms", "idle_share"])
def test_throughput_reader_reads_as_its_metric(name):
    """``<m>.throughput`` reads what ``<m>`` reads, with a trace and
    without one."""
    red = SimpleNamespace(program_time=lambda p: (0.5, 2), busy_s=3.0,
                          window_s=4.0)
    for red_ in (red, None):
        r = _readings({"window": (0, 10), "recorder": _recorder([])},
                      red=red_)
        assert registry.load_reader(f"{name}.throughput")(r) == \
            registry.load_reader(name)(r)
    assert registry.load_reader(f"{name}.throughput")(
        _readings({"window": (0, 1), "recorder": _recorder([])},
                  red=red)) is not None


@pytest.mark.parametrize("value,ok", [(0.1, True), (0.3, False),
                                      (float("nan"), False)])
def test_check_compares_a_number_with_its_limit(value, ok):
    from chipbench.drive_common import Check
    c = Check()
    c.compare("max_logit_gap", value, 0.25)
    assert c.ok is ok
    json.dumps({"correct": c.ok, "check": c.numbers})


def test_sample_holds_the_longest_and_stops_at_its_token_share():
    from chipbench.drive_common import Served, sample
    served = [Served([1], [0] * n) for n in (3, 9, 2, 5, 4, 1)]
    for seed in (1, 2, 2**40):
        pick = sample(served, seed, tokens=12, max_requests=4)
        assert pick[0] is served[1]                 # the longest first
        n = sum(len(s.out) for s in pick)
        assert n >= 12 or len(pick) == 4
        assert n - len(pick[-1].out) < 12           # stops once reached
        assert len({id(s) for s in pick}) == len(pick) <= 4
    assert sample([], 1, 12, 4) == []


def test_served_of_takes_finished_requests_with_their_engine():
    from chipbench.drive_common import served_of
    done = SimpleNamespace(output_ids=[5, 6], done=True,
                           prompt_ids=[1, 999, 3], max_new_tokens=4)
    running = _req(2, done=False)
    got = served_of([Tracked(done), Tracked(running)], max_len=64,
                    vocab=100, stream=1, engine="cloud0")
    assert len(got) == 1
    assert (got[0].ctx, got[0].out) == ([1, 99, 3], [5, 6])
    assert (got[0].stream, got[0].engine) == (1, "cloud0")


def test_mfu_decode_counts_each_decode_row_at_its_context():
    """Two requests decode in the traced second: their rows' operations
    per step over the decode program's time per execution."""
    fam = registry.load_family("dense")
    model = json.loads((registry.PKG / "configs" / "internlm2-1.8b.json")
                       .read_text())
    sz = fam.sizes(model)
    a = Tracked(SimpleNamespace(prompt_ids=[1] * 10, max_new_tokens=8),
                times=[0.5, 1.2, 1.4, 2.5])
    b = Tracked(SimpleNamespace(prompt_ids=[1] * 30, max_new_tokens=8),
                times=[1.2, 1.4])
    red = SimpleNamespace(program_time=lambda p: (0.004, 2)
                          if p == "jit_decode_fn" else (0.0, 0))
    r = e2e.Readings(
        cell=SimpleNamespace(family=fam,
                             cell={"deployment": {"max_len": 4096}}),
        result={"window": (0, 3), "recorder": _recorder([a, b]),
                "sizes": sz},
        ctx=SimpleNamespace(trace_host=(1.0, 2.0)), t_start=0.0,
        peaks={"flops_bf16": 197e12}, reduced=red)
    # a's tokens 1 and 2 attend 11 and 12 lines; b's token 0 is prefill's
    # and its token 1 attends 31; two steps, 2 ms each
    flops = sum(fam.decode_flops(sz, n) for n in (11, 12, 31))
    want = 100.0 * (flops / 2) / 0.002 / 197e12
    assert registry.load_reader("mfu.decode")(r) == pytest.approx(want)
    assert 0.5 < want < 2.0
    r.ctx = SimpleNamespace(trace_host=None)
    assert registry.load_reader("mfu.decode")(r) is None
