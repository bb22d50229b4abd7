"""Entry ``fleet``: the HybridFlow deployment that ``repro.launch.serve``
builds, driven through ``ServingRuntime.serve_trace``.

An edge ``ServingEngine`` and a cloud ``EnginePool`` of replicas serve the
same architecture from two weight streams of the seed; the router is
warm-started offline as the launcher does; queries come from the
program's benchmark generator in equal shares and arrive open loop. The
fleet clock starts at the ``serve`` call, so the window is
``[preroll_s, preroll_s + seconds)`` on it.
"""
from __future__ import annotations

import threading
import time

from chipbench import drive_common as C
from chipbench import traffic, weights
from chipbench.recorder import Recorder

RECOVERY = ("retries", "degraded", "exec_faults", "timeouts",
            "cloud_deaths", "cloud_failovers")
# past the drain limit, the seconds a device step may still take before
# the process is ended as hung
HANG_S = 60.0


class DrainLimit(Exception):
    """Raised from the fleet's pump loop once the drain limit is past."""


def _stop_at(executor, deadline: float) -> None:
    """Make one executor's ``pump`` end the fleet's loop past ``deadline``
    (no engine step is in flight between two pumps)."""
    pump = executor.pump

    def pump_or_stop():
        if time.perf_counter() > deadline:
            raise DrainLimit
        return pump()

    executor.pump = pump_or_stop


def _dag_ok(res, sched) -> str:
    """'' when every subtask of the query ran after its dependencies and
    produced tokens, else what is wrong."""
    sids = set(res.dag.sids)
    if set(res.results) != sids:
        return f"{res.qid}: no result for {sorted(sids - set(res.results))}"
    empty = [s for s, r in res.results.items() if r.tok_out <= 0]
    if empty:
        return f"{res.qid}: subtasks {empty} produced no tokens"
    span = {sid: (s, e) for s, e, sid, _ in sched.events}
    for nd in res.dag.nodes:
        for d in nd.deps:
            if d in span and nd.sid in span and \
                    span[nd.sid][0] + 1e-9 < span[d][1]:
                return f"{res.qid}: subtask {nd.sid} started before " \
                       f"its dependency {d} ended"
    return ""


def run(ctx) -> dict:
    from repro.core.hybridflow import HybridFlowPolicy
    from repro.core.planner import SyntheticPlanner
    from repro.core.profiler import train_default_router
    from repro.core.scheduler import Schedule
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.serving import ServingConfig, ServingRuntime
    from repro.serving.engine import JAXExecutor

    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    mix, dep, model = cell.traffic, cell.cell["deployment"], cell.model
    cfg = C.model_config(model)
    sizes = weights.Sizes.of(model)
    words = [weights.seed_words(seed, 0), weights.seed_words(seed, 1)]
    edge_engine = C.build_engine(cfg, C.program_params(cfg, sizes, words[0]),
                                 dep, slots=dep["edge_slots"])
    cloud_engine = C.build_engine(cfg, C.program_params(cfg, sizes, words[1]),
                                  dep, slots=dep["cloud_slots"])
    wm = WorldModel()
    edge = JAXExecutor(edge_engine, wm, cloud=False,
                       concurrency=int(dep["edge_concurrency"]))
    cloud = JAXExecutor(cloud_engine, wm, cloud=True,
                        price_out=float(dep["price_out"]))
    ctx.mark("engines")
    router, _ = train_default_router(n_queries=int(dep["router_queries"]),
                                     epochs=int(dep["router_epochs"]))
    ctx.mark("router")
    policy = HybridFlowPolicy(router, tau0=float(dep["tau0"]),
                              k_max=float(dep["k_max"]), wm=wm)
    runtime = ServingRuntime(
        edge, cloud, policy, planner=SyntheticPlanner(),
        config=ServingConfig(max_inflight=int(dep["max_inflight"]),
                             replicas=int(dep["cloud_replicas"])))
    pool = runtime.cloud.engine
    replicas = list(getattr(pool, "engines", [pool]))
    rec = Recorder()
    logs = [rec.wrap_engine(edge_engine, "edge")] + [
        rec.wrap_engine(e, f"cloud{i}") for i, e in enumerate(replicas)]
    engines = [edge_engine] + replicas
    rec.wrap_method(router, "predict", "router_predict")
    for ex in (runtime.edge, runtime.cloud):
        rec.wrap_method(ex, "submit", "dispatch")
    if pool is not cloud_engine:
        rec.wrap_method(pool, "pump", "pool_pump")

    lo_rel, hi_rel = traffic.window_of(mix, seconds)
    times = traffic.arrivals(mix, seconds)
    queries, warm_qs = traffic.fleet_queries(
        mix, seconds, gen_benchmark,
        extra=int(mix.get("warm_queries", 0)))
    ctx.note(f"queries {len(queries)} digest "
             f"{traffic.digest((q.qid, q.text, len(q.subtasks)) for q in queries)} "
             f"arrivals {traffic.digest(times)}")
    steps = C.warm_engine(edge_engine, cell.cell.get("warm_edge", []),
                          sizes.vocab, seed)
    steps += C.warm_engine(replicas[0], cell.cell.get("warm_cloud", []),
                           sizes.vocab, seed)
    if warm_qs:
        runtime.serve(warm_qs)
    ctx.mark("warm", warm_steps=steps, warm_queries=len(warm_qs))

    planner = runtime.planner
    scheds, dags = [], []
    for q in queries:
        s, (dag, status) = Schedule(), planner.plan(q)
        runtime.submit(q, dag, plan_status=status, schedule_out=s)
        scheds.append(s)
        dags.append(dag)
    start = [len(log.tracked) for log in logs]
    t_call = time.perf_counter()
    w0, w1 = t_call + lo_rel, t_call + hi_rel
    ctx.open_window(w0, w1, engines)
    deadline = w1 + float(mix.get("drain_s", 60.0))
    for ex in (runtime.edge, runtime.cloud):
        _stop_at(ex, deadline)
    done = threading.Event()
    ctx.watchdog(done, deadline + HANG_S)
    try:
        report = runtime.serve(arrivals=times)
    except DrainLimit:
        report = None
    done.set()
    ctx.close_window(engines)
    ctx.stop_trace()
    t_end = time.perf_counter()

    due = [i for i, t in enumerate(times) if lo_rel <= t < hi_rel]
    failed = 0
    if report is None:
        # the drain limit came first: a query due in the window whose
        # DAG has not completed never answered
        for i in due:
            left = set(dags[i].sids) - {e[2] for e in scheds[i].events}
            failed += bool(left)
        ctx.check.require(False, f"{failed} of the {len(due)} queries due "
                          f"in the window unfinished at the drain limit")
        stats, due_results = {}, []
    else:
        for i, (res, sch) in enumerate(zip(report.results, scheds)):
            why = _dag_ok(res, sch)
            ctx.check.require(not why, why)
            failed += bool(why) and lo_rel <= times[i] < hi_rel
        stats = report.stats
        due_results = [report.results[i] for i in due]
        for k in RECOVERY:
            ctx.check.require(not stats.get(k), f"recovery counter {k}="
                              f"{stats.get(k)} (a masked device error)")
    result = {
        "recorder": rec, "window": (w0, w1), "attempted": len(due),
        "failed": failed, "queries_due": due_results,
        "counters": {"fleet": {k: v for k, v in stats.items()
                               if isinstance(v, (int, float))}},
        "drain_s": t_end - w1, "sizes": sizes,
        # what the window's queries took, in arrival order, for the log
        "detail": [(queries[i].benchmark, len(queries[i].subtasks),
                    round(r.queue_wait, 3), round(r.latency, 3))
                   for i, r in zip(due, due_results)],
    }
    ctx.memory_peak = C.peak_bytes()
    corr = cell.cell["correct"]
    # every engine that served a request is in the sample, with an equal
    # share of the served tokens; the edge's weights are stream 0, the
    # cloud replicas' stream 1
    served = [C.served_of(log.tracked[n:], int(dep["max_len"]), sizes.vocab,
                          stream=int(log is not logs[0]), engine=log.name)
              for log, n in zip(logs, start)]
    served = [s_ for s_ in served if s_]
    pick = []
    for i, s_ in enumerate(served):
        pick += C.sample(s_, seed + i, int(corr["sample_tokens"]) //
                         len(served), int(corr["max_requests"]) // len(served))
    ctx.check.require(bool(served) and {r.engine for r in pick} ==
                      {s_[0].engine for s_ in served},
                      "the sample misses an engine that served requests")
    rec.release()
    del edge_engine, cloud_engine, edge, cloud, runtime, pool, replicas
    del engines, logs, report, scheds
    C.free_device()
    result["reference"] = C.check_served(ctx.check, model, words, pick,
                                         float(corr["limit"]),
                                         controls=ctx.controls)
    return result
