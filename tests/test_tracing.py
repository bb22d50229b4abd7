"""Program spans (``repro.tracing``) and the counters beside them.

A tiny fleet served under a profiler trace on the CPU: every span the
scheduler, executor, pool and engine open is in the trace's host plane,
and the spans nest as the host phases do. Then the counters, by hand:
``SubtaskResult.dispatch_wait`` in the simulated-clock loop, and the
engine's ``decode_rows`` / ``prefill_positions``."""
import glob

import jax
import pytest

from repro.core.dag import Node, PlanDAG
from repro.core.hybridflow import StaticPolicy
from repro.core.planner import SyntheticPlanner
from repro.core.scheduler import FleetScheduler, WorldModelExecutor
from repro.data.tasks import Query, Subtask, WorldModel, gen_benchmark
from repro.serving.engine import JAXExecutor, ServingEngine
from repro.serving.pool import EnginePool
from repro.tracing import span

SPANS = ("hf.sched.pass", "hf.sched.poll", "hf.sched.complete",
         "hf.sched.route", "hf.sched.dispatch", "hf.sched.nap",
         "hf.exec.submit", "hf.pool.step", "hf.engine.step",
         "hf.engine.admit", "hf.engine.prefill_launch",
         "hf.engine.decode_launch", "hf.engine.sync")

# jaxlib warns once when it first builds the type of an event's stats
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


def _host_spans(log_dir):
    """{name: [(start_ns, end_ns, thread, stats)]} of the ``hf.*`` events
    in the trace's host plane; a thread is its line's index."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for t, line in enumerate(pl.lines):
            for e in line.events:
                if e.name.startswith("hf."):
                    s = float(e.start_ns)
                    out.setdefault(e.name, []).append(
                        (s, s + float(e.duration_ns), t, dict(e.stats)))
    return out


def _inside(iv, outers):
    s, e = iv[0], iv[1]
    return any(o[0] <= s and e <= o[1] for o in outers)


def test_span_names_its_phase_under_the_hf_prefix(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("sched.route"):
            with span("exec.submit", qid="q7", sid=3):
                pass
    finally:
        jax.profiler.stop_trace()
    got = _host_spans(tmp_path)
    assert set(got) == {"hf.sched.route", "hf.exec.submit"}
    (sub,) = got["hf.exec.submit"]
    assert sub[3] == {"qid": "q7", "sid": 3}
    assert _inside(sub, got["hf.sched.route"])


def test_traced_tiny_fleet_has_every_span_nested_by_thread(model_zoo,
                                                           tmp_path):
    """Edge engine on the scheduler's thread, a two-replica threaded
    pool for the cloud; query 0 goes to the edge, queries 1 and 2 to the
    cloud at one arrival time, so both replicas step on worker threads.
    The first arrival lies in the future, so the loop naps first."""
    cfg, params = model_zoo("qwen2-1.5b")
    wm = WorldModel()
    edge = JAXExecutor(ServingEngine(cfg, params, batch_slots=2,
                                     max_len=128), wm, cloud=False,
                       concurrency=1)
    pool = EnginePool.replicate(cfg, params, replicas=2, batch_slots=2,
                                max_len=128, prefill_chunk=32)
    cloud = JAXExecutor(pool, wm, cloud=True, price_out=3.2e-5)
    fleet = FleetScheduler(edge, cloud, max_inflight=4, pump=True)
    planner = SyntheticPlanner()
    for i, q in enumerate(gen_benchmark("gpqa", 3)):
        dag, status = planner.plan(q)
        fleet.submit(q, dag, StaticPolicy(int(i > 0)), plan_status=status,
                     arrival=0.05 if i == 0 else 0.06)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        results = fleet.run()
    finally:
        jax.profiler.stop_trace()
    assert len(results) == 3
    # the edge query's four siblings of subtask 0 take the one edge slot
    # in turn: all but the first wait for it
    waits = [s.dispatch_wait for s in results[0].results.values()]
    assert min(waits) >= 0 and sum(w > 0 for w in waits) >= 3
    got = _host_spans(tmp_path)
    assert set(SPANS) <= set(got), sorted(set(SPANS) - set(got))
    assert not set(got) - set(SPANS)
    passes = got["hf.sched.pass"]
    sched_thread = {t for _, _, t, _ in passes}
    assert len(sched_thread) == 1
    # per-subtask spans carry their ids; per-pass and per-step ones none
    subtasks = sum(len(r.results) for r in results)
    assert len(got["hf.exec.submit"]) == subtasks
    assert all(set(st) == {"qid", "sid"}
               for *_, st in got["hf.exec.submit"])
    assert not any(st for *_, st in passes + got["hf.engine.step"])
    pool_steps = got["hf.pool.step"]
    assert {t for _, _, t, _ in pool_steps} == sched_thread
    on_sched = [iv for iv in got["hf.engine.step"] if iv[2] in sched_thread]
    on_workers = [iv for iv in got["hf.engine.step"]
                  if iv[2] not in sched_thread]
    assert on_sched and on_workers
    assert all(_inside(iv, passes) for iv in on_sched)
    assert all(_inside(iv, pool_steps) for iv in on_workers)
    for name in ("hf.sched.poll", "hf.sched.complete", "hf.sched.nap"):
        assert all(_inside(iv, passes) for iv in got[name]), name
    # the device read sits inside a step on the same thread
    steps = got["hf.engine.step"]
    for iv in got["hf.engine.sync"]:
        assert _inside(iv, [s for s in steps if s[2] == iv[2]])


# ---- counters ---------------------------------------------------------

def _diamond():
    """0 -> {1, 2} -> 3."""
    sts = (Subtask(0, "explain the question", "EXPLAIN", (), 0.3, 60, 80),
           Subtask(1, "analyze branch a", "ANALYZE", (0,), 0.5, 80, 120),
           Subtask(2, "analyze branch b", "ANALYZE", (0,), 0.6, 80, 120),
           Subtask(3, "generate the answer", "GENERATE", (1, 2), 0.4, 90,
                   140))
    nodes = tuple(Node(s.sid, s.desc, s.role, s.deps, requires=s.requires)
                  for s in sts)
    return Query("diamond-0", "gpqa", "diamond test query", sts), \
        PlanDAG(nodes)


def test_dispatch_wait_is_the_wait_for_a_free_edge_slot():
    """Two parallel edge subtasks at edge concurrency 1: the first starts
    when it is routed, the second waits out the first's latency."""
    wm = WorldModel()
    fleet = FleetScheduler(WorldModelExecutor(wm, cloud=False,
                                              concurrency=1),
                           WorldModelExecutor(wm, cloud=True,
                                              concurrency=8))
    q, dag = _diamond()
    fleet.submit(q, dag, StaticPolicy(0))
    (res,) = fleet.run()
    r = res.results
    assert r[0].dispatch_wait == 0.0
    assert r[1].dispatch_wait == 0.0
    assert r[2].dispatch_wait == pytest.approx(r[1].latency, abs=1e-12)
    assert r[3].dispatch_wait == 0.0


def test_dispatch_wait_is_zero_with_a_free_slot_for_each_subtask():
    wm = WorldModel()
    fleet = FleetScheduler(WorldModelExecutor(wm, cloud=False,
                                              concurrency=2),
                           WorldModelExecutor(wm, cloud=True,
                                              concurrency=2))
    q, dag = _diamond()
    fleet.submit(q, dag, StaticPolicy(0))
    (res,) = fleet.run()
    assert all(s.dispatch_wait == 0.0 for s in res.results.values())


@pytest.mark.parametrize("prompts,want", [
    # one request, 20 prompt tokens, chunk 16: prefill 16 (width 16),
    # then 4 (width 8); decode 3 steps of 2 rows
    ([list(range(1, 21))],
     dict(prefill_tokens=20, prefill_positions=24, tokens_out=3,
          decode_rows=6, steps=3)),
    # 20 and 5 tokens together: prefill [16, 5] at width 16, B decodes
    # alone; then [4] at width 8 and both decode twice, then A alone
    ([list(range(1, 21)), list(range(100, 105))],
     dict(prefill_tokens=25, prefill_positions=40, tokens_out=6,
          decode_rows=8, steps=4)),
], ids=["one", "two"])
def test_engine_counts_computed_rows_and_positions(model_zoo, prompts,
                                                   want):
    cfg, params = model_zoo("qwen2-1.5b")
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                        prefill_chunk=16)
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_done()
    # counts assume no early EOS: every request ran to max_new_tokens
    assert all(len(r.output_ids) == 4 for r in reqs)
    assert {k: eng.stats[k] for k in want} == want
