"""Fleet serving throughput: concurrent multi-query runtime vs the seed's
sequential one-query-at-a-time loop — analytic executors AND real JAX
engines.

Analytic section: for each in-flight level the same query stream runs
through the HybridFlow scheduler twice — once admitted all together
(bounded by ``max_inflight``), once back-to-back — reporting queries per
simulated second, p50/p99 per-query makespan, accuracy and API cost.

Real-engine section: the same fleet drives a ``JAXExecutor`` pair
(reduced-config models decoding for real) in two modes —

* ``real-sync``  — the pre-pump synchronous dispatch (``pump=False``):
  each subtask blocks in ``Executor.run`` and drains alone, so engine
  ``peak_active`` stays 1;
* ``real-pump``  — the async pump loop: co-scheduled subtasks decode in
  the same micro-batches via batched chunked prefill + batched decode.

The pump mode must beat the synchronous wall-clock by >= 1.3x (the
overlap is the whole point).

Pooled-cloud section: the same pumped fleet drives a cloud that is
either ONE serving engine (``real-cloud-single`` — the pre-pool shape,
capacity = its slot count) or an ``EnginePool`` of R replicas
(``real-cloud-poolR`` — capacity R x slots, least-loaded dispatch,
launch-all/commit-all pump passes). The pooled cloud must beat the
single engine on concurrent fleet wall-clock: extra replica slots drain
the cloud backlog sooner and each pass overlaps one replica's host
bookkeeping with another's device compute.

Degraded section: the pooled fleet runs clean (``real-faultfree``) and
under a seeded chaos plan (``real-degraded`` — 10% injected cloud submit
failures + one replica crash mid-run) with scheduler retry/degradation
armed, reporting the wall-clock overhead of absorbing the faults plus
the recovery counters (retries/timeouts/degraded/failovers/deaths).
Every query must still complete or the bench itself fails.

Open-loop section: a fixed seeded bursty arrival trace (base load, a
burst, a long zero-traffic gap, one post-gap arrival; wall-compressed)
replays with timed admission against an elastic 0→R cloud pool
(``real-openloop`` row — TTFT/queue-wait percentiles at the measured
offered RPS plus the autoscale event counters). The section hard-fails
unless every query completes, the pool scales to zero during the gap,
and the post-gap arrival pokes it back to warm. A separate analytic
``trace-gen`` row records the Poisson generator's measured mean RPS
against its target (``check_bench`` gates it within 5%).

Prefix-reuse section: a shared-system-prompt fleet shaped like the
executor's DAG prompts runs reuse-off then reuse-on through a direct
engine in deterministic subtask waves (``prefix-reuse-off`` /
``prefix-reuse-on`` rows). Bit-identity and exact token accounting
hard-fail inside the section; the rows' ``savings_pct`` / ``hit_rate``
metrics are pure functions of the prompt set, so they GATE in CI like
the analytic rows (reuse must keep skipping >= 40% of prefill work).
``--prefix-fleet N`` adds the heavy live-runtime twin (``real-prefix-*``
rows, nightly): the full pumped DAG fleet with scheduler prefix hints,
warn-only like every real-* row.

Two final sections microbench the serving attention ops themselves —
jnp reference vs Pallas kernel for ragged chunked prefill
(``prefill-ref`` / ``prefill-pallas`` rows) and for batched decode
(``decode-ref`` / ``decode-pallas`` rows). Results are also written as
machine-readable ``BENCH_serve.json`` rows ``{mode, qps, p50, p99,
prefill_tokens, peak_active, ...}`` for the cross-PR perf trajectory
(diffed against ``benchmarks/baseline_serve.json`` by
``benchmarks/check_bench.py`` in CI — the analytic and kernel-microbench
rows gate, the noisy real-engine wall-clock rows warn; the microbench
check also requires the Pallas row to beat its jnp reference row in the
same run).

``PYTHONPATH=src python -m benchmarks.serve_throughput [--queries N]
[--real-queries M] [--pool-queries K] [--json PATH]``
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import common as C
from repro.core.hybridflow import HybridFlowPolicy
from repro.serving.runtime import ServingConfig, ServingRuntime

INFLIGHT_LEVELS = (2, 4, 8, 16)
MIN_REAL_SPEEDUP = 1.3


def _runtime(pipe, router, **kw):
    policy = HybridFlowPolicy(router, wm=pipe.wm)
    return ServingRuntime(pipe.edge, pipe.cloud, policy,
                          planner=pipe.planner, config=ServingConfig(**kw))


def run(n_queries=None, bench="gpqa"):
    n = n_queries or max(32, min(C.N_QUERIES, 64))
    pipe = C.shared_pipeline(0)
    router = C.shared_router()
    qs = C.queries(bench, n)

    rows = []
    seq = _runtime(pipe, router).serve(qs, mode="sequential")
    rows.append(["sequential", 1, n, seq.makespan, seq.qps,
                 seq.p50_latency, seq.p99_latency, seq.accuracy,
                 seq.api_cost])
    for m in INFLIGHT_LEVELS:
        rep = _runtime(pipe, router, max_inflight=m).serve(qs)
        rows.append([f"concurrent-{m}", m, n, rep.makespan, rep.qps,
                     rep.p50_latency, rep.p99_latency, rep.accuracy,
                     rep.api_cost])
        assert rep.stats["peak_inflight"] == min(m, n)
        if rep.qps <= seq.qps:
            print(f"WARNING: concurrent-{m} qps {rep.qps:.3f} did not beat "
                  f"sequential {seq.qps:.3f}")
    header = ["mode", "max_inflight", "queries", "makespan_s", "qps",
              "p50_s", "p99_s", "accuracy", "api_usd"]
    return header, rows


class _HashRoutePolicy:
    """Deterministic per-node routing (cloud unless sid % 3 == 0): the
    same decisions regardless of completion order, so sync vs pump run
    identical work and the wall-clock comparison is fair."""

    def decide(self, query, node, ctx):
        return int(node.sid % 3 != 0), {}

    def observe(self, query, node, r, result, ctx):
        pass


def run_real(n_queries=6, bench="gpqa", *, arch="qwen2-1.5b",
             max_inflight=8):
    """Real-JAX-engine fleet: synchronous dispatch vs the async pump."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import SyntheticPlanner
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.models import model as M
    from repro.serving.engine import JAXExecutor, ServingEngine

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    wm = WorldModel()
    qs = gen_benchmark(bench, n_queries)

    def serve(pump: bool):
        edge_e = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                               prefill_chunk=64)
        cloud_e = ServingEngine(cfg, params, batch_slots=4, max_len=160,
                                prefill_chunk=64)
        edge = JAXExecutor(edge_e, wm, cloud=False, concurrency=1)
        cloud = JAXExecutor(cloud_e, wm, cloud=True, concurrency=4,
                            price_out=3.2e-5)
        rt = ServingRuntime(edge, cloud, _HashRoutePolicy(),
                            planner=SyntheticPlanner(),
                            config=ServingConfig(max_inflight=max_inflight,
                                                 pump=pump))
        rep = rt.serve(qs)
        return rep, edge_e, cloud_e

    # warm-up BOTH modes: each produces its own prefill-group shapes
    # (pump: G>=2 per call; sync: G=1), so jit compiles must be paid
    # outside either timed window for a fair wall-clock comparison
    serve(True)
    serve(False)
    rows = []
    for mode, pump in (("real-sync", False), ("real-pump", True)):
        rep, edge_e, cloud_e = serve(pump)
        rows.append({
            "mode": mode,
            "queries": n_queries,
            "qps": rep.n / rep.wall_s if rep.wall_s > 0 else 0.0,
            "p50": rep.p50_latency,
            "p99": rep.p99_latency,
            "wall_s": rep.wall_s,
            "prefill_tokens": (edge_e.stats["prefill_tokens"]
                               + cloud_e.stats["prefill_tokens"]),
            "peak_active": max(edge_e.stats["peak_active"],
                               cloud_e.stats["peak_active"]),
            "prefill_batch_max": max(edge_e.stats["prefill_batch_max"],
                                     cloud_e.stats["prefill_batch_max"]),
        })
    speedup = rows[0]["wall_s"] / max(rows[1]["wall_s"], 1e-9)
    return rows, speedup


class _CloudBoundPolicy:
    """Every subtask to the cloud: the pooled section measures how cloud
    capacity scales, so the fleet must actually saturate the cloud pool
    (a mixed policy stalls on the 1-wide edge at every DAG root and the
    cloud never backs up)."""

    def decide(self, query, node, ctx):
        return 1, {}

    def observe(self, query, node, r, result, ctx):
        pass


def run_pool(n_queries=12, bench="gpqa", *, arch="qwen2-1.5b", replicas=2,
             slots=4, max_inflight=None):
    """Pooled-vs-single cloud under the pumped fleet: the same cloud
    engine shape as ``run_real`` (``slots`` KV slots) either alone (the
    pre-pool single cloud engine) or sharded across ``replicas``
    EnginePool replicas, drained by a cloud-bound query stream deep
    enough to keep every replica's slots leased."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import SyntheticPlanner
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.models import model as M
    from repro.serving.engine import JAXExecutor, ServingEngine
    from repro.serving.pool import EnginePool

    if replicas < 2:
        raise ValueError("run_pool compares a pooled cloud against the "
                         "single engine; needs replicas >= 2")
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    wm = WorldModel()
    qs = gen_benchmark(bench, n_queries)
    max_inflight = max_inflight or n_queries

    def serve(R):
        edge_e = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                               prefill_chunk=64)
        if R == 1:   # the existing single-engine cloud path, unpooled
            cloud_eng = ServingEngine(cfg, params, batch_slots=slots,
                                      max_len=160, prefill_chunk=64)
        else:
            cloud_eng = EnginePool.replicate(cfg, params, replicas=R,
                                             batch_slots=slots, max_len=160,
                                             prefill_chunk=64)
        edge = JAXExecutor(edge_e, wm, cloud=False, concurrency=1)
        cloud = JAXExecutor(cloud_eng, wm, cloud=True, price_out=3.2e-5)
        rt = ServingRuntime(edge, cloud, _CloudBoundPolicy(),
                            planner=SyntheticPlanner(),
                            config=ServingConfig(max_inflight=max_inflight,
                                                 pump=True))
        rep = rt.serve(qs)
        return rep, cloud_eng

    # one warm-up pays every jit compile for BOTH modes: _jit_steps is a
    # module-level cache keyed on (cfg, max_len, backend), which single
    # engine and pool replicas share (same shapes throughout)
    serve(replicas)
    rows = []
    for mode, R in (("real-cloud-single", 1),
                    (f"real-cloud-pool{replicas}", replicas)):
        rep, cloud_eng = serve(R)
        stats = cloud_eng.stats
        rows.append({
            "mode": mode,
            "queries": n_queries,
            "cloud_replicas": R,
            "cloud_capacity": cloud_eng.capacity,
            "qps": rep.n / rep.wall_s if rep.wall_s > 0 else 0.0,
            "p50": rep.p50_latency,
            "p99": rep.p99_latency,
            "wall_s": rep.wall_s,
            # per-replica high-water marks (their sum can overstate true
            # concurrency; the per-replica list is the honest evidence
            # that every replica's slots were leased)
            "replica_peak_active": [o["peak_active"]
                                    for o in cloud_eng.occupancy()]
            if hasattr(cloud_eng, "occupancy")
            else [stats["peak_active"]],
            "replica_requests": rep.stats.get("cloud_replica_requests",
                                              [stats["requests"]]),
        })
    speedup = rows[0]["wall_s"] / max(rows[1]["wall_s"], 1e-9)
    # every replica must have taken work (least-loaded dispatch spreads
    # a saturating fleet across the whole pool)
    assert all(n > 0 for n in rows[1]["replica_requests"]), \
        rows[1]["replica_requests"]
    return rows, speedup


def run_degraded(n_queries=12, bench="gpqa", *, arch="qwen2-1.5b",
                 replicas=2, slots=4):
    """Chaos-overhead section: the same pumped cloud-bound fleet runs
    clean (``real-faultfree``) and under a seeded fault plan
    (``real-degraded`` — 10% injected cloud submit failures plus one
    replica crash mid-run) with scheduler recovery armed. Every query
    must still complete; the row records the wall-clock overhead of
    riding out the faults (retry backoff + failover restarts + degraded
    edge decodes) next to the recovery counters that explain it."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import SyntheticPlanner
    from repro.core.scheduler import RetryPolicy
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.models import model as M
    from repro.serving.engine import JAXExecutor, ServingEngine
    from repro.serving.faults import FaultPlan
    from repro.serving.pool import EnginePool

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    wm = WorldModel()
    qs = gen_benchmark(bench, n_queries)

    def serve(faults, retry):
        edge_e = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                               prefill_chunk=64)
        pool = EnginePool.replicate(cfg, params, replicas=replicas,
                                    batch_slots=slots, max_len=160,
                                    prefill_chunk=64)
        edge = JAXExecutor(edge_e, wm, cloud=False, concurrency=1)
        cloud = JAXExecutor(pool, wm, cloud=True, price_out=3.2e-5)
        rt = ServingRuntime(edge, cloud, _CloudBoundPolicy(),
                            planner=SyntheticPlanner(),
                            config=ServingConfig(max_inflight=n_queries,
                                                 pump=True, faults=faults,
                                                 retry=retry))
        return rt.serve(qs)

    serve(None, None)                     # jit compiles outside both timings
    plan = FaultPlan(seed=0, submit_fail_rate=0.10, crash_replica=((1, 20),))
    retry = RetryPolicy(max_retries=2, timeout_s=30.0)
    rows = []
    for mode, faults, rp in (("real-faultfree", None, None),
                             ("real-degraded", plan, retry)):
        rep = serve(faults, rp)
        assert all(r is not None and len(r.results) == r.dag.n
                   for r in rep.results), f"{mode}: dropped a query"
        s = rep.stats
        rows.append({
            "mode": mode,
            "queries": n_queries,
            "cloud_replicas": replicas,
            "qps": rep.n / rep.wall_s if rep.wall_s > 0 else 0.0,
            "p50": rep.p50_latency,
            "p99": rep.p99_latency,
            "wall_s": rep.wall_s,
            "retries": s.get("retries", 0),
            "timeouts": s.get("timeouts", 0),
            "degraded": s.get("degraded", 0),
            "failovers": s.get("cloud_failovers", 0),
            "deaths": s.get("cloud_deaths", 0),
            "injected_submit_faults":
                s.get("injected", {}).get("submit_faults", 0),
        })
    rows[1]["overhead_pct"] = 100.0 * (
        rows[1]["wall_s"] / max(rows[0]["wall_s"], 1e-9) - 1.0)
    return rows, rows[1]["overhead_pct"]


def run_prefix(n_queries=6, *, arch="qwen2-1.5b", subtasks=4,
               max_new=8):
    """KV prefix-reuse fidelity + savings section (GATES in CI).

    A shared-system-prompt fleet shaped like the executor's DAG prompts
    (per-query context + per-subtask tail) runs twice through a direct
    engine — reuse off, then on — submitted in deterministic subtask
    waves (wave j = subtask j of every query; ``batch_slots=n_queries``
    keeps each query on its own slot, so the reuse pattern is a pure
    function of the prompts, not of timing). The section hard-fails
    unless greedy outputs are bit-identical and the token accounting is
    exact (``off prefill == on prefill + saved``); the emitted
    ``prefix-reuse-off`` / ``prefix-reuse-on`` rows carry the
    deterministic savings/hit-rate metrics ``check_bench`` gates on."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.engine import ServingEngine

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # per-query shared context ~64 tokens (4 full PREFIX_BLOCKs); tails
    # ~25 tokens — reuse-on should skip >= 40% of prefill work
    ctx = {q: (f"[query {q:02d}] You are a careful assistant; reason "
               f"step by step about case {q:02d}. ")
           for q in range(n_queries)}
    waves = [[ctx[q] + f"subtask {j}: analyze aspect {j} of it"
              for q in range(n_queries)] for j in range(subtasks)]

    def serve(reuse: bool):
        eng = ServingEngine(cfg, params, batch_slots=n_queries, max_len=160,
                            prefill_chunk=32, prefix_reuse=reuse)
        outs = []
        t0 = time.perf_counter()
        for wave in waves:
            reqs = [eng.submit(p, max_new_tokens=max_new) for p in wave]
            eng.run_until_done()
            outs += [tuple(r.output_ids) for r in reqs]
        return outs, eng.stats, time.perf_counter() - t0

    serve(True)                                # pay jit compiles
    serve(False)
    off_out, off, off_s = serve(False)
    on_out, on, on_s = serve(True)
    assert on_out == off_out, \
        "prefix reuse broke bit-identity on the shared-prefix fleet"
    assert off["prefill_tokens"] == \
        on["prefill_tokens"] + on["prefill_tokens_saved"], \
        (off["prefill_tokens"], on["prefill_tokens"],
         on["prefill_tokens_saved"])
    n_req = n_queries * subtasks
    rows = []
    for mode, st, wall in (("prefix-reuse-off", off, off_s),
                           ("prefix-reuse-on", on, on_s)):
        saved = st["prefill_tokens_saved"]
        rows.append({
            "mode": mode,
            "queries": n_queries,
            "requests": n_req,
            "wall_s": wall,
            "tokens_out": st["tokens_out"],
            "prefill_tokens": st["prefill_tokens"],
            "prefill_tokens_saved": saved,
            "prefix_hits": st["prefix_hits"],
            "prefix_copies": st["prefix_copies"],
            # deterministic gating metrics: fraction of the no-reuse
            # prefill work skipped, and hits per reusable request
            "savings_pct": 100.0 * saved / max(off["prefill_tokens"], 1),
            "hit_rate": st["prefix_hits"] / max(n_req - n_queries, 1),
        })
    return rows, rows[1]["savings_pct"]


def run_prefix_fleet(n_queries=6, bench="gpqa", *, arch="qwen2-1.5b"):
    """Heavy live-runtime twin of :func:`run_prefix` (nightly): the full
    ServingRuntime DAG fleet — planner, pump loop, DAG prefix hints,
    pool-less executors — served with reuse on vs off. Answers must
    match exactly (greedy outputs depend only on the prompt, so the
    per-subtask answer map is dispatch-order-independent); the
    ``real-prefix-*`` rows record the wall-clock and prefill-token
    effect at fleet scale and WARN (never gate) like every real-* row."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import SyntheticPlanner
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.models import model as M
    from repro.serving.engine import JAXExecutor, ServingEngine

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    wm = WorldModel()
    qs = gen_benchmark(bench, n_queries)

    def serve(reuse: bool):
        edge_e = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                               prefill_chunk=64, prefix_reuse=reuse)
        cloud_e = ServingEngine(cfg, params, batch_slots=4, max_len=160,
                                prefill_chunk=64, prefix_reuse=reuse,
                                seed=1)
        edge = JAXExecutor(edge_e, wm, cloud=False, concurrency=1)
        cloud = JAXExecutor(cloud_e, wm, cloud=True, price_out=3.2e-5)
        rt = ServingRuntime(edge, cloud, _HashRoutePolicy(),
                            planner=SyntheticPlanner(),
                            config=ServingConfig(max_inflight=n_queries,
                                                 pump=True))
        rep = rt.serve(qs)
        answers = sorted((r.qid, s.sid, s.answer) for r in rep.results
                         for s in r.results.values())
        return rep, answers, edge_e, cloud_e

    serve(True)                                # pay jit compiles
    serve(False)
    rows = []
    maps = {}
    for mode, reuse in (("real-prefix-off", False), ("real-prefix-on", True)):
        rep, answers, edge_e, cloud_e = serve(reuse)
        maps[mode] = answers
        rows.append({
            "mode": mode,
            "queries": n_queries,
            "qps": rep.n / rep.wall_s if rep.wall_s > 0 else 0.0,
            "p50": rep.p50_latency,
            "p99": rep.p99_latency,
            "wall_s": rep.wall_s,
            "prefill_tokens": (edge_e.stats["prefill_tokens"]
                               + cloud_e.stats["prefill_tokens"]),
            "prefill_tokens_saved":
                (edge_e.stats["prefill_tokens_saved"]
                 + cloud_e.stats["prefill_tokens_saved"]),
            "prefix_hits": (edge_e.stats["prefix_hits"]
                            + cloud_e.stats["prefix_hits"]),
        })
    assert maps["real-prefix-on"] == maps["real-prefix-off"], \
        "prefix reuse changed a fleet answer (bit-identity broken)"
    saved = rows[1]["prefill_tokens_saved"]
    return rows, 100.0 * saved / max(rows[0]["prefill_tokens"], 1)


def run_trace_gen(*, rps=4.0, duration=600.0, seed=7):
    """Analytic trace-generator fidelity row (gates in CI): a seeded
    Poisson trace at a target RPS must measure within 5% of it over a
    long horizon, and the same seed must replay identically. Purely
    host-side arithmetic — deterministic on any machine."""
    from repro.serving.traffic import Trace

    tr = Trace.poisson(rps, duration, seed=seed)
    replay = Trace.poisson(rps, duration, seed=seed)
    assert tr.arrivals == replay.arrivals, \
        "trace generator is not deterministic under a fixed seed"
    return [{"mode": "trace-gen", "n": tr.n, "duration": duration,
             "seed": seed, "target_rps": rps, "measured_rps": tr.mean_rps,
             "rps_err_pct": 100.0 * abs(tr.mean_rps - rps) / rps}]


# the open-loop section's fixed trace: steady base load, a burst, a 20s
# zero-traffic gap, one post-gap arrival (seed 0 guarantees it) — then
# wall-compressed so the whole replay fits a bench run
_OPENLOOP_TRACE = dict(base_rps=0.12, duration=60.0, burst_rps=0.8,
                       burst_at=15.0, burst_s=5.0, gap_at=28.0, gap_s=20.0,
                       seed=0)


def run_openloop(bench="gpqa", *, arch="qwen2-1.5b", replicas=4,
                 scale=1 / 6):
    """Open-loop elastic serving: replay the fixed seeded bursty trace
    with timed admission against an elastic 0→``replicas`` cloud pool
    (scale-to-zero + modeled cold start armed). The row reports TTFT /
    queue-wait percentiles at the measured offered RPS plus the
    autoscale counters; the section itself hard-fails unless every query
    completes, the pool scales to zero during the gap, and the post-gap
    arrival pokes it back to warm."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.planner import SyntheticPlanner
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.models import model as M
    from repro.serving import (AutoscalePolicy, ColdStartModel, EnginePool,
                               Trace)
    from repro.serving.engine import JAXExecutor, ServingEngine

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    wm = WorldModel()

    def build(autoscale):
        edge_e = ServingEngine(cfg, params, batch_slots=2, max_len=160,
                               prefill_chunk=64)
        pool = EnginePool.replicate(cfg, params, replicas=replicas,
                                    batch_slots=2, max_len=160,
                                    prefill_chunk=64)
        edge = JAXExecutor(edge_e, wm, cloud=False, concurrency=1)
        cloud = JAXExecutor(pool, wm, cloud=True, price_out=3.2e-5)
        return ServingRuntime(edge, cloud, _CloudBoundPolicy(),
                              planner=SyntheticPlanner(),
                              config=ServingConfig(max_inflight=None,
                                                   pump=True,
                                                   autoscale=autoscale))

    build(None).serve(gen_benchmark(bench, 2))   # pay jit compiles

    trace = Trace.bursty(**_OPENLOOP_TRACE).scaled(scale)
    auto = AutoscalePolicy(min_replicas=0, scale_up_at=0.8,
                           scale_down_at=0.3, idle_to_zero_s=0.6,
                           cold_start=ColdStartModel(0.1, 0.15, 0.05))
    rep = build(auto).serve_trace(trace, gen_benchmark(bench, trace.n))
    a = rep.trace["autoscale"]
    assert all(r is not None and len(r.results) == r.dag.n
               for r in rep.results), "open loop dropped a query"
    assert a["scale_to_zero"] >= 1, \
        f"pool never scaled to zero during the gap: {a['events']}"
    assert a["pokes"] >= 2, \
        f"post-gap arrival never poked the pool warm: {a['events']}"
    return [{
        "mode": "real-openloop",
        "queries": trace.n,
        "trace": trace.label,
        "trace_seed": trace.seed,
        "offered_rps": rep.trace["offered_rps"],
        "qps": rep.n / rep.wall_s if rep.wall_s > 0 else 0.0,
        "p50": rep.p50_latency,
        "p99": rep.p99_latency,
        "ttft_p50": rep.p50_ttft,
        "ttft_p99": rep.p99_ttft,
        "queue_p99": rep.queue_wait_percentile(99.0),
        "wall_s": rep.wall_s,
        "cloud_replicas": replicas,
        "scale_ups": a["scale_ups"],
        "scale_downs": a["scale_downs"],
        "scale_to_zero": a["scale_to_zero"],
        "pokes": a["pokes"],
        "promotions": a["promotions"],
    }]


def run_prefill_microbench(*, G=4, S=64, W=256, H=4, KV=2, hd=64, iters=3):
    """Ref-vs-kernel ragged chunked-prefill attention microbench.

    Times the exact op ``serve_prefill_chunk`` dispatches per layer — the
    jnp reference twin vs the Pallas ragged kernel — on one engine-shaped
    workload (G chunk rows, ragged take/pos0, kv_width=W). On CPU the
    kernel runs in interpret mode, so treat these numbers as a
    plumbing/trajectory check; on a TPU the kernel compiles.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models import layers as L

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (G, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (G, W, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (G, W, KV, hd), jnp.float32)
    take = jax.random.randint(ks[3], (G,), 1, S + 1).astype(jnp.int32)
    pos0 = jax.random.randint(ks[4], (G,), 0, W + 1 - take).astype(jnp.int32)
    n_tok = int(np.asarray(take).sum())

    ref_fn = jax.jit(lambda q, k, v, p, t: L.ragged_prefill_attention(
        q, k, v, pos0=p, take=t))

    def timed(fn):
        fn(q, k, v, pos0, take).block_until_ready()      # warm-up/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v, pos0, take)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    rows = []
    for mode, fn in (("prefill-ref", ref_fn),
                     ("prefill-pallas", ops.ragged_prefill_attention)):
        dt = timed(fn)
        rows.append({"mode": mode, "G": G, "S": S, "kv_width": W,
                     "heads": H, "kv_heads": KV, "head_dim": hd,
                     "ms_per_call": dt * 1e3,
                     "prefill_tok_per_s": n_tok / dt if dt > 0 else 0.0})
    return rows


def run_decode_microbench(*, B=8, M=256, H=4, KV=2, hd=64, iters=10):
    """Ref-vs-kernel batched decode-attention microbench.

    Times the exact op ``_dispatch_attention`` routes per decode tick —
    the jnp reference (``decode_attention_ref``) vs the batched Pallas
    decode kernel (one (B, M/bk) launch for all slots) — on an
    engine-shaped workload (B slots, ragged per-slot ``kv_len`` over an
    M-line cache). Same caveat as the prefill microbench: interpret mode
    on CPU, a real speed comparison on TPU.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, M, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, M, KV, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, M + 1).astype(jnp.int32)

    ref_fn = jax.jit(lambda q, k, v, n: ref.decode_attention_ref(q, k, v, n))
    ker_fn = jax.jit(lambda q, k, v, n: ops.decode_attention(
        q, k, v, kv_len=n))

    def timed(fn):
        fn(q, k, v, kv_len).block_until_ready()          # warm-up/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v, kv_len)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    rows = []
    for mode, fn in (("decode-ref", ref_fn), ("decode-pallas", ker_fn)):
        dt = timed(fn)
        rows.append({"mode": mode, "B": B, "cache_len": M,
                     "heads": H, "kv_heads": KV, "head_dim": hd,
                     "ms_per_call": dt * 1e3,
                     "decode_tok_per_s": B / dt if dt > 0 else 0.0})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queries", type=int, default=None,
                    help="analytic-section query count")
    ap.add_argument("--real-queries", type=int, default=6,
                    help="real-engine-section query count (0 disables)")
    ap.add_argument("--pool-queries", type=int, default=12,
                    help="pooled-vs-single cloud section query count "
                         "(0 disables; needs to be deep enough to keep "
                         "every replica's slots leased)")
    ap.add_argument("--pool-replicas", type=int, default=2,
                    help="cloud pool replicas for the pooled section")
    ap.add_argument("--degraded-queries", type=int, default=12,
                    help="chaos-overhead section query count: clean vs "
                         "10%% injected cloud faults + a replica crash "
                         "(0 disables)")
    ap.add_argument("--openloop-replicas", type=int, default=4,
                    help="elastic cloud pool ceiling for the open-loop "
                         "trace-replay section (0 disables)")
    ap.add_argument("--prefix-queries", type=int, default=6,
                    help="KV prefix-reuse fidelity section query count "
                         "(deterministic, gates in CI; 0 disables)")
    ap.add_argument("--prefix-fleet", type=int, default=0,
                    help="heavy live-runtime prefix-reuse fleet query "
                         "count (nightly; 0 disables)")
    ap.add_argument("--benchmark", default="gpqa")
    ap.add_argument("--json", default="BENCH_serve.json",
                    help="machine-readable output path ('' disables)")
    ap.add_argument("--prefill-iters", type=int, default=3,
                    help="ref-vs-kernel prefill microbench iterations "
                         "(0 disables)")
    ap.add_argument("--decode-iters", type=int, default=10,
                    help="ref-vs-kernel decode microbench iterations "
                         "(0 disables)")
    args = ap.parse_args()

    header, rows = run(args.queries, args.benchmark)
    C.print_csv("serve_throughput", header, rows)
    seq_qps = rows[0][4]
    best = max(rows[1:], key=lambda r: r[4])
    print(f"\nbest: {best[0]} at {best[4]:.3f} q/s "
          f"({best[4] / seq_qps:.2f}x sequential)")

    json_rows = [dict(zip(["mode", "max_inflight", "queries", "makespan_s",
                           "qps", "p50", "p99", "accuracy", "api_usd"], r),
                      prefill_tokens=None, peak_active=None) for r in rows]

    tg_rows = run_trace_gen()
    C.print_csv("serve_trace_gen", list(tg_rows[0].keys()),
                [list(r.values()) for r in tg_rows])
    print(f"\ntrace generator: {tg_rows[0]['measured_rps']:.3f} rps "
          f"measured vs {tg_rows[0]['target_rps']:.3f} target "
          f"({tg_rows[0]['rps_err_pct']:.2f}% err; CI gates at 5%)")
    json_rows += tg_rows

    if args.real_queries > 0:
        real_rows, speedup = run_real(args.real_queries, args.benchmark)
        C.print_csv("serve_throughput_real",
                    list(real_rows[0].keys()),
                    [list(r.values()) for r in real_rows])
        print(f"\nreal-engine pump speedup: {speedup:.2f}x wall-clock over "
              f"synchronous dispatch (target >= {MIN_REAL_SPEEDUP}x)")
        if speedup < MIN_REAL_SPEEDUP:
            print(f"WARNING: pump speedup {speedup:.2f}x below "
                  f"{MIN_REAL_SPEEDUP}x target")
        json_rows += real_rows

    if args.pool_queries > 0:
        pool_rows, pspeed = run_pool(args.pool_queries, args.benchmark,
                                     replicas=args.pool_replicas)
        C.print_csv("serve_cloud_pool",
                    list(pool_rows[0].keys()),
                    [list(r.values()) for r in pool_rows])
        print(f"\npooled-cloud speedup: {pspeed:.2f}x wall-clock over the "
              f"single cloud engine (R={args.pool_replicas}, "
              f"capacity {pool_rows[1]['cloud_capacity']} vs "
              f"{pool_rows[0]['cloud_capacity']})")
        if pspeed < 1.0:
            print(f"WARNING: pooled cloud did not beat the single engine "
                  f"({pspeed:.2f}x)")
        json_rows += pool_rows

    if args.degraded_queries > 0:
        deg_rows, overhead = run_degraded(args.degraded_queries,
                                          args.benchmark)
        C.print_csv("serve_degraded",
                    [k for k in deg_rows[1].keys()],
                    [[r.get(k) for k in deg_rows[1].keys()]
                     for r in deg_rows])
        print(f"\nchaos overhead: {overhead:+.1f}% wall-clock to absorb "
              f"{deg_rows[1]['injected_submit_faults']} injected faults "
              f"+ {deg_rows[1]['deaths']} replica death(s) "
              f"({deg_rows[1]['retries']} retries, "
              f"{deg_rows[1]['degraded']} degraded, "
              f"{deg_rows[1]['failovers']} failovers) — all "
              f"{deg_rows[1]['queries']} queries completed")
        json_rows += deg_rows

    if args.openloop_replicas > 0:
        ol_rows = run_openloop(args.benchmark,
                               replicas=args.openloop_replicas)
        C.print_csv("serve_openloop", list(ol_rows[0].keys()),
                    [list(r.values()) for r in ol_rows])
        r = ol_rows[0]
        print(f"\nopen loop: {r['queries']} queries at "
              f"{r['offered_rps']:.2f} rps offered — ttft p50 "
              f"{r['ttft_p50']:.2f}s p99 {r['ttft_p99']:.2f}s | autoscale "
              f"ups={r['scale_ups']} downs={r['scale_downs']} "
              f"to_zero={r['scale_to_zero']} pokes={r['pokes']}")
        json_rows += ol_rows

    if args.prefix_queries > 0:
        px_rows, px_save = run_prefix(args.prefix_queries)
        C.print_csv("serve_prefix", list(px_rows[0].keys()),
                    [list(r.values()) for r in px_rows])
        print(f"\nprefix reuse: {px_save:.1f}% of prefill tokens skipped "
              f"({px_rows[1]['prefix_hits']} hits, "
              f"{px_rows[1]['prefix_copies']} cross-slot copies) with "
              f"bit-identical greedy outputs — CI gates savings >= 40%")
        json_rows += px_rows

    if args.prefix_fleet > 0:
        pxf_rows, pxf_save = run_prefix_fleet(args.prefix_fleet,
                                              args.benchmark)
        C.print_csv("serve_prefix_fleet", list(pxf_rows[0].keys()),
                    [list(r.values()) for r in pxf_rows])
        print(f"\nprefix reuse (live fleet): {pxf_save:.1f}% prefill "
              f"tokens skipped; wall {pxf_rows[0]['wall_s']:.2f}s off -> "
              f"{pxf_rows[1]['wall_s']:.2f}s on, same answers")
        json_rows += pxf_rows

    if args.prefill_iters > 0:
        pf_rows = run_prefill_microbench(iters=args.prefill_iters)
        C.print_csv("serve_prefill_microbench",
                    list(pf_rows[0].keys()),
                    [list(r.values()) for r in pf_rows])
        json_rows += pf_rows

    if args.decode_iters > 0:
        dec_rows = run_decode_microbench(iters=args.decode_iters)
        C.print_csv("serve_decode_microbench",
                    list(dec_rows[0].keys()),
                    [list(r.values()) for r in dec_rows])
        json_rows += dec_rows

    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_rows, f, indent=2)
        print(f"wrote {args.json} ({len(json_rows)} rows)")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
