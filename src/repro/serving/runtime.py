"""Multi-query serving runtime: the fleet-level front-end to HybridFlow.

``ServingRuntime`` admits many ``Query`` objects at once, plans each one
(unless a pre-planned DAG is supplied), and drains them through the shared
``FleetScheduler`` event loop: ready subtasks from *all* in-flight queries
multiplex onto one edge pool and one cloud pool with round-robin fairness,
bounded admission (``max_inflight``), optional fleet-wide budget caps and
optional cloud→edge spill under saturation. Per-query budgets stay where
the paper puts them — inside the routing policy's ``TwoBudgetThreshold``
duals — while the runtime adds the *global* dual the single-query code
had no place for.

Quickstart (analytic world-model executors)::

    from repro.core.hybridflow import Pipeline, HybridFlowPolicy
    from repro.core.profiler import train_default_router
    from repro.data.tasks import gen_benchmark
    from repro.serving.runtime import ServingConfig, ServingRuntime

    pipe = Pipeline()                      # edge + cloud executor pair
    router, _ = train_default_router()
    policy = HybridFlowPolicy(router, wm=pipe.wm)
    rt = ServingRuntime(pipe.edge, pipe.cloud, policy,
                        planner=pipe.planner,
                        config=ServingConfig(max_inflight=8,
                                             global_k_max=1.0))
    report = rt.serve(gen_benchmark("gpqa", 32))
    print(report.qps, report.p50_latency, report.p99_latency)

All runtime knobs live on the frozen :class:`ServingConfig`; the PR 8
flat-kwargs deprecation shim is gone (its one-release window is up), so
``ServingRuntime(edge, cloud, policy, planner=, config=)`` is the whole
constructor surface and anything else raises ``TypeError``. One
dispatcher runs every mode::

    rt.serve(queries)                          # closed loop (fleet)
    rt.serve(queries, mode="sequential")       # one-at-a-time baseline
    rt.serve(queries, arrivals=trace)          # open loop (timed admission)
    rt.serve_trace(trace, queries)             # alias for the above

Open-loop serving replays a ``serving.traffic.Trace`` (seeded Poisson /
day-cycle / burst arrival schedules): queries enter the fleet at their
arrival times, per-query TTFT and queue wait land on each
``QueryResult``, and ``report.trace`` carries offered-vs-served RPS plus
any autoscaler decisions. An elastic cloud
(``ServingConfig(replicas=R, autoscale=AutoscalePolicy(...))``) grows
and shrinks warm replicas from live occupancy, pays a modeled cold
start, scales to zero in traffic gaps and re-warms on the first arrival
after one (see ``serving.pool``).

The same runtime drives real JAX engines by passing ``JAXExecutor`` pairs
(see ``examples/serve_hybrid.py``). Async executors are auto-detected and
drained through the fleet scheduler's *pump loop*: every dispatch is a
``submit`` into the executor's serving engine, the loop keeps stepping
each engine while routing continues, and co-scheduled subtasks from
different queries decode in the same micro-batches — wall-clock then
tracks the simulated makespan instead of serializing subtask-by-subtask.
``pump=False`` forces the pre-pump synchronous dispatch (the perf
baseline in ``benchmarks/serve_throughput.py``); latency is measured
wall-clock from actual batched decode steps either way. ``replicas=R``
shards an engine-backed cloud executor across an R-replica
``EnginePool`` (shared params, independent KV slot pools, least-loaded
dispatch): cloud concurrency then derives from pool capacity and the
report's stats carry per-replica occupancy.

Fault tolerance: ``retry=RetryPolicy(...)`` arms scheduler-side recovery
(retry w/ backoff, deadline timeouts, cloud→edge degradation — see
``core.scheduler``), and ``faults=`` injects deterministic chaos (a
``FaultPlan``, a pre-built ``FaultInjector``, or a spec string like
``"submit_fail=0.1,crash=1@8,seed=3"`` — see ``serving.faults``): the
cloud executor (and with ``edge=1`` the edge too) is wrapped for
submit-failure/stall injection and an ``EnginePool``-backed cloud gets
its replicas wrapped for crash/straggler injection. Passing ``faults``
without ``retry`` defaults to ``RetryPolicy()`` — injecting failures
with recovery disarmed would only prove the fleet can crash. Fault and
recovery counters land in ``report.stats`` (``injected``, ``retries``,
``timeouts``, ``degraded``, ``cloud_deaths``/``cloud_failovers``…).
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from repro.core.dag import PlanDAG
from repro.core.dual import TwoBudgetThreshold
from repro.core.scheduler import (Executor, FleetScheduler, QueryResult,
                                  RetryPolicy, RoutingPolicy, Schedule)
from repro.data.tasks import Query
from repro.serving.traffic import Trace


@dataclass(frozen=True)
class ServingConfig:
    """Every ``ServingRuntime`` knob in one frozen value object.

    Admission & budgets:
      * ``max_inflight`` — concurrently admitted queries (None = no cap)
      * ``global_k_max`` / ``global_l_max`` — fleet-wide $ / wall-clock
        budget caps (see ``_global_threshold``)
      * ``spill_to_edge`` — re-route cloud-bound work to an idle edge
        slot when the cloud is saturated

    Drivers & capacity:
      * ``pump`` — event-loop driver: True = real-time pump loop,
        False = synchronous dispatch, None = auto-detect from executors
      * ``replicas`` — shard an engine-backed cloud executor across an
        R-replica ``EnginePool``
      * ``autoscale`` — an ``AutoscalePolicy`` making that pool elastic
        (requires a pooled, engine-backed cloud)

    Fault tolerance:
      * ``retry`` — scheduler-side recovery (``RetryPolicy``)
      * ``faults`` — deterministic chaos: a ``FaultPlan``, a built
        ``FaultInjector`` or a spec string ("submit_fail=0.1,...")
      * ``stall_grace`` — idle seconds the pumped driver tolerates
        before declaring the fleet stalled (recovery armed only)
    """

    max_inflight: Optional[int] = 8
    global_k_max: Optional[float] = None
    global_l_max: Optional[float] = None
    spill_to_edge: bool = False
    pump: Optional[bool] = None
    replicas: Optional[int] = None
    autoscale: Optional["AutoscalePolicy"] = None  # noqa: F821 (lazy import)
    retry: Optional[RetryPolicy] = None
    faults: object = None
    stall_grace: float = 5.0


@dataclass
class RuntimeReport:
    """Fleet-level outcome of one ``serve`` call (any mode)."""

    results: List[QueryResult]
    makespan: float            # simulated fleet makespan (s)
    wall_s: float              # real wall-clock spent inside the loop
    stats: Dict[str, int] = field(default_factory=dict)
    # open-loop only: offered traffic + autoscale outcome (None otherwise,
    # keeping the closed-loop report shape exactly as before)
    trace: Optional[Dict[str, object]] = None

    @property
    def n(self) -> int:
        return len(self.results)

    @property
    def qps(self) -> float:
        """Queries per simulated second (fleet throughput)."""
        return self.n / self.makespan if self.makespan > 0 else 0.0

    @property
    def accuracy(self) -> float:
        if not self.results:
            return 0.0
        return float(np.mean([r.final_correct for r in self.results]))

    @property
    def api_cost(self) -> float:
        return float(sum(r.api_cost for r in self.results))

    def latency_percentile(self, p: float) -> float:
        """Percentile of per-query makespans (admission -> finish)."""
        if not self.results:
            return 0.0
        return float(np.percentile([r.latency for r in self.results], p))

    @property
    def p50_latency(self) -> float:
        return self.latency_percentile(50)

    @property
    def p99_latency(self) -> float:
        return self.latency_percentile(99)

    def ttft_percentile(self, p: float) -> float:
        """Percentile of per-query TTFT (arrival -> first completed
        subtask); meaningful for open-loop runs."""
        if not self.results:
            return 0.0
        return float(np.percentile([r.ttft for r in self.results], p))

    @property
    def p50_ttft(self) -> float:
        return self.ttft_percentile(50)

    @property
    def p99_ttft(self) -> float:
        return self.ttft_percentile(99)

    def queue_wait_percentile(self, p: float) -> float:
        """Percentile of per-query admission wait (arrival -> admission)."""
        if not self.results:
            return 0.0
        return float(np.percentile([r.queue_wait for r in self.results], p))

    def summary(self) -> str:
        line = (f"{self.n} queries | makespan {self.makespan:.2f}s | "
                f"{self.qps:.2f} q/s | acc {self.accuracy:.2f} | "
                f"p50 {self.p50_latency:.2f}s p99 {self.p99_latency:.2f}s | "
                f"API ${self.api_cost:.4f}")
        if self.trace is not None:
            line += (f" | offered {self.trace['offered_rps']:.2f} rps | "
                     f"ttft p50 {self.p50_ttft:.2f}s "
                     f"p99 {self.p99_ttft:.2f}s | queue p99 "
                     f"{self.queue_wait_percentile(99):.2f}s")
        return line


def _global_threshold(k_max: Optional[float],
                      l_max: Optional[float]) -> Optional[TwoBudgetThreshold]:
    """Fleet-wide dual: tau hits 1.0 when k_used/k_max + l_used/l_max
    reaches 1 — with one cap set that is exactly that budget's exhaustion;
    with both set the *sum* of fractional spends is capped (a linear
    combined budget, conservative by construction). That is the point
    where FleetScheduler starts forcing edge routing."""
    if k_max is None and l_max is None:
        return None
    k = math.inf if k_max is None else max(k_max, 0.0) / 2.0
    l = math.inf if l_max is None else max(l_max, 0.0) / 2.0
    # a zero cap means "no cloud budget at all": exhausted from the start
    tau0 = 1.0 if (k == 0.0 or l == 0.0) else 0.0
    return TwoBudgetThreshold(tau0=tau0, k_max=k or math.inf,
                              l_max=l or math.inf)


class ServingRuntime:
    """Admit -> plan -> fleet-execute many queries over shared pools."""

    def __init__(self, edge: Executor, cloud: Executor,
                 policy: RoutingPolicy, *, planner=None,
                 config: Optional[ServingConfig] = None):
        # the PR 8 flat-kwargs deprecation shim served its one-release
        # window and is gone: every runtime knob lives on ServingConfig,
        # and an unknown kwarg is a plain TypeError from Python itself
        cfg = config if config is not None else ServingConfig()
        self.config = cfg
        self.edge = edge
        self.cloud = self._pooled_cloud(cloud, cfg.replicas)
        self._arm_autoscale(cfg.autoscale)
        self.policy = policy
        self.planner = planner
        self.max_inflight = cfg.max_inflight
        self.global_k_max = cfg.global_k_max
        self.global_l_max = cfg.global_l_max
        self.spill_to_edge = cfg.spill_to_edge
        self.pump = cfg.pump
        self.stall_grace = cfg.stall_grace
        self.fault_injector = self._make_injector(cfg.faults)
        # chaos without recovery would only prove the fleet can crash
        self.retry = cfg.retry \
            if cfg.retry is not None or cfg.faults is None \
            else RetryPolicy()
        self._wrap_faulty()
        self.global_budget: Optional[TwoBudgetThreshold] = None
        self._pending: List[Tuple[Query, PlanDAG, str,
                                  Optional[Schedule]]] = []

    def _arm_autoscale(self, policy) -> None:
        """Make the (pooled, engine-backed) cloud elastic."""
        if policy is None:
            return
        from repro.serving.pool import EnginePool
        eng = getattr(self.cloud, "engine", None)
        if not isinstance(eng, EnginePool):
            raise ValueError(
                "autoscale= needs an EnginePool-backed cloud executor — "
                "pass ServingConfig(replicas=R, autoscale=...) or build "
                "the JAXExecutor over an EnginePool yourself")
        eng.arm_autoscale(policy)

    @staticmethod
    def _make_injector(faults):
        """Accept a spec string, a FaultPlan, or a ready FaultInjector."""
        if faults is None:
            return None
        from repro.serving.faults import FaultInjector, FaultPlan
        if isinstance(faults, FaultInjector):
            return faults
        if isinstance(faults, str):
            faults = FaultPlan.parse(faults)
        return FaultInjector(faults)

    def _wrap_faulty(self) -> None:
        """Install the fault plan: wrap executors for submit/stall
        injection and pool replicas for crash/straggler injection."""
        inj = self.fault_injector
        if inj is None:
            return
        plan = inj.plan
        if plan.has_replica_faults:
            from repro.serving.pool import EnginePool
            eng = getattr(self.cloud, "engine", None)
            if not isinstance(eng, EnginePool):
                raise ValueError(
                    "replica faults (crash=/slow=) need an EnginePool-"
                    "backed cloud executor (pass replicas=R)")
            inj.wrap_pool(eng)
        if plan.has_executor_faults:
            self.cloud = inj.wrap_executor(self.cloud, side="cloud")
            if plan.edge_faults:
                self.edge = inj.wrap_executor(self.edge, side="edge")

    @staticmethod
    def _pooled_cloud(cloud: Executor, replicas: Optional[int]) -> Executor:
        """Thread ``replicas=`` through to the cloud side: scale an
        engine-backed cloud executor out to an R-replica ``EnginePool``
        (shared params, independent KV slot pools). Dispatch concurrency
        then derives from pool capacity (replicas × slots) — unless the
        caller set an explicit cap on the executor, which is an admission
        policy and survives pooling unchanged — and cloud→edge spill
        fires only when every replica is saturated. ``None`` leaves the
        executor untouched (including pre-built pools)."""
        if replicas is None:
            return cloud
        if replicas < 1:
            raise ValueError("replicas must be >= 1 (or None)")
        eng = getattr(cloud, "engine", None)
        if eng is None:
            raise ValueError(
                "replicas= needs an engine-backed cloud executor "
                "(JAXExecutor); analytic executors model cloud width "
                "through their concurrency directly")
        from repro.serving.engine import JAXExecutor
        from repro.serving.pool import EnginePool
        if isinstance(eng, EnginePool):
            if eng.n_replicas != replicas:
                raise ValueError(
                    f"cloud executor already holds a {eng.n_replicas}-"
                    f"replica pool; cannot rescale to replicas={replicas}")
            return cloud
        pool = EnginePool.like(eng, replicas)
        keep_cap = None if getattr(cloud, "derived_concurrency", True) \
            else cloud.concurrency
        return JAXExecutor(pool, cloud.wm, cloud=True,
                           concurrency=keep_cap,
                           price_out=cloud.price_out)

    def _pool_occupancy(self, stats: Dict) -> Dict:
        """Attach per-replica slot-lease stats for engine-backed pools,
        plus KV prefix-reuse counters for any engine-backed side.

        Runs after the fleet loop returns, so no pool worker is live:
        reading engine ``stats`` (replica-private under the
        thread-ownership contract — see ``serving/__init__`` and
        tools/reprolint/README.md) is safe here without any barrier."""
        for name, ex in (("edge", self.edge), ("cloud", self.cloud)):
            eng = getattr(ex, "engine", None)
            est = getattr(eng, "stats", None)
            if est is not None and "prefix_hits" in est:
                stats[f"{name}_prefix_hits"] = est["prefix_hits"]
                stats[f"{name}_prefill_tokens_saved"] = \
                    est["prefill_tokens_saved"]
            occ = getattr(eng, "occupancy", None)
            if occ is None:
                continue
            stats[f"{name}_replicas"] = eng.n_replicas
            stats[f"{name}_replica_requests"] = [o["requests"]
                                                 for o in occ()]
            stats[f"{name}_pump_passes"] = eng.pool_stats["pump_passes"]
            for key in ("deaths", "failovers", "suspects", "hedges",
                        "replica_errors"):
                if key in eng.pool_stats:
                    stats[f"{name}_{key}"] = eng.pool_stats[key]
            health = getattr(eng, "health", None)
            if health is not None:
                stats[f"{name}_replica_health"] = list(health)
            scaler = getattr(eng, "autoscaler", None)
            if scaler is not None:
                stats[f"{name}_lifecycle"] = list(eng.lifecycle)
                stats[f"{name}_autoscale"] = scaler.summary()
        if self.fault_injector is not None:
            stats["injected"] = dict(self.fault_injector.stats)
        return stats

    # ---- admission ----------------------------------------------------
    def submit(self, query: Query, dag: Optional[PlanDAG] = None, *,
               plan_status: str = "valid",
               schedule_out: Optional[Schedule] = None) -> int:
        """Enqueue one query; plans it if no DAG is supplied."""
        if dag is None:
            if self.planner is None:
                raise ValueError("no DAG given and no planner configured")
            dag, plan_status = self.planner.plan(query)
        self._pending.append((query, dag, plan_status, schedule_out))
        return len(self._pending) - 1

    # ---- execution ----------------------------------------------------
    def serve(self, queries: Sequence[Query] = (), *,
              arrivals: Union[Trace, Sequence[float], None] = None,
              mode: str = "fleet") -> RuntimeReport:
        """One dispatcher for every serving mode.

        * ``mode="fleet"`` (default), no ``arrivals`` — closed loop:
          drain everything submitted (plus ``queries``) concurrently.
        * ``mode="fleet"``, ``arrivals=`` a ``Trace`` or a sequence of
          arrival times (seconds, one per query in submit order) — open
          loop: queries enter the fleet at their arrival times and the
          report carries TTFT / queue-wait / offered-RPS metrics.
        * ``mode="sequential"`` — the one-query-at-a-time baseline
          (delegates to ``serve_sequential``; no arrivals).
        """
        if mode == "sequential":
            if arrivals is not None:
                raise ValueError("arrivals= requires mode='fleet'")
            return self.serve_sequential(queries)
        if mode != "fleet":
            raise ValueError(f"unknown serve mode {mode!r} "
                             f"(expected 'fleet' or 'sequential')")
        for q in queries:
            self.submit(q)
        batch, self._pending = self._pending, []
        times: Optional[List[float]] = None
        if arrivals is not None:
            times = [float(a) for a in arrivals]
            if len(times) != len(batch):
                raise ValueError(
                    f"arrivals length {len(times)} != {len(batch)} "
                    f"queries (one arrival time per query, submit order)")
        self.global_budget = _global_threshold(self.global_k_max,
                                               self.global_l_max)
        fleet = FleetScheduler(self.edge, self.cloud,
                               max_inflight=self.max_inflight,
                               global_budget=self.global_budget,
                               spill_to_edge=self.spill_to_edge,
                               pump=self.pump, retry=self.retry,
                               stall_grace=self.stall_grace)
        for i, (q, dag, status, sched) in enumerate(batch):
            fleet.submit(q, dag, self.policy, plan_status=status,
                         schedule_out=sched,
                         arrival=times[i] if times else 0.0)
        t0 = time.perf_counter()
        results = fleet.run()
        wall = time.perf_counter() - t0
        report = RuntimeReport(
            results, fleet.makespan, wall,
            stats=self._pool_occupancy(dict(fleet.stats)))
        if times is not None:
            report.trace = self._trace_summary(arrivals, times, report)
        return report

    def serve_trace(self, trace: Trace,
                    queries: Sequence[Query] = ()) -> RuntimeReport:
        """Replay an open-loop arrival trace: ``len(trace)`` queries
        (submitted + ``queries``) enter the fleet at the trace's arrival
        times. Alias for ``serve(queries, arrivals=trace)``."""
        return self.serve(queries, arrivals=trace)

    def _trace_summary(self, arrivals, times: List[float],
                       report: RuntimeReport) -> Dict[str, object]:
        """Offered-vs-served traffic summary attached to the report."""
        horizon = arrivals.duration if isinstance(arrivals, Trace) \
            else (max(times) if times else 0.0)
        out: Dict[str, object] = {
            "n": len(times),
            "duration": float(horizon),
            "offered_rps": len(times) / horizon if horizon > 0 else 0.0,
            "served_rps": report.qps,
        }
        if isinstance(arrivals, Trace):
            out["label"] = arrivals.label
            out["seed"] = arrivals.seed
            out["target_rps"] = arrivals.target_rps
        scaler = getattr(getattr(self.cloud, "engine", None),
                         "autoscaler", None)
        if scaler is not None:
            out["autoscale"] = scaler.summary()
        return out

    def serve_sequential(self, queries: Sequence[Query] = ()) -> RuntimeReport:
        """One-query-at-a-time baseline (the seed's serving shape): each
        query runs alone on the pools; fleet makespan is the plain sum."""
        for q in queries:
            self.submit(q)
        batch, self._pending = self._pending, []
        self.global_budget = _global_threshold(self.global_k_max,
                                               self.global_l_max)
        results: List[QueryResult] = []
        stats: Dict[str, int] = {}
        makespan = 0.0
        t0 = time.perf_counter()
        for q, dag, status, sched in batch:
            fleet = FleetScheduler(self.edge, self.cloud,
                                   global_budget=self.global_budget,
                                   pump=self.pump, retry=self.retry,
                                   stall_grace=self.stall_grace)
            fleet.submit(q, dag, self.policy, plan_status=status,
                         schedule_out=sched)
            results.extend(fleet.run())
            makespan += fleet.makespan
            for k, v in fleet.stats.items():
                stats[k] = stats.get(k, 0) + v
        wall = time.perf_counter() - t0
        stats["peak_inflight"] = 1 if batch else 0
        return RuntimeReport(results, makespan, wall,
                             stats=self._pool_occupancy(stats))
