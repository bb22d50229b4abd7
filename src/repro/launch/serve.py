"""Serving launcher: a HybridFlow edge/cloud deployment over two serving
engines driven by the concurrent multi-query runtime.

Queries are admitted together into ``ServingRuntime``: their ready
subtasks share the edge engine's KV slots and the cloud pool via the
fleet scheduler's async pump loop — every dispatch ``submit``s into a
real engine, the loop keeps stepping both engines while routing
continues, and co-scheduled subtasks decode in the same micro-batches
(batched chunked prefill + batched device-side sampling).
``--cloud-replicas R`` shards the cloud engine across an R-replica
``EnginePool`` (least-loaded dispatch, cloud concurrency = replicas x
slots); ``--no-pump`` forces the old synchronous per-subtask dispatch;
``--sequential`` restores the seed's one-query-at-a-time loop;
``--global-k-max`` caps fleet-wide API spend. Cross-request KV prefix
reuse is ON by default (sibling subtasks share their query's context
prefix; the final stats line reports hits and prefill tokens skipped)
— ``--no-prefix-reuse`` disables it, ``--prefix-block`` tunes the hash
granularity.

Open loop: ``--rps R`` generates a seeded Poisson arrival trace and
replays it with timed admission (``--trace FILE`` replays a recorded
``Trace`` JSON instead); the report then carries TTFT / queue-wait
percentiles at the measured offered RPS. ``--autoscale`` makes the
cloud pool elastic — occupancy-driven grow/shrink with a modeled cold
start, scale-to-zero on traffic gaps, poke-to-warm on the next
arrival. Example::

  PYTHONPATH=src python -m repro.launch.serve --reduced --rps 0.8 \
      --duration 15 --cloud-replicas 3 --autoscale

``--faults SPEC`` drives a chaos run: deterministic seeded fault
injection (cloud submit failures, stalls, replica crash/stragglers —
see ``serving.faults.FaultPlan.parse``) absorbed by scheduler-side
recovery (``--max-retries`` / ``--timeout`` / ``--backoff-base``) and
pool-side replica failover. Example::

  PYTHONPATH=src python -m repro.launch.serve --reduced --queries 12 \
      --cloud-replicas 2 --faults "submit_fail=0.1,crash=1@20,seed=0"

Both engines serve their architecture at its published widths, with
weights and KV cache in the config's dtype (bfloat16). Each engine holds
its whole model on one device, so the pair must fit there: on one TPU
v5e, ``--edge-arch qwen2-1.5b --cloud-arch qwen2-1.5b --max-len 2048``
does (``chip_smoke.py`` runs exactly that). ``--reduced`` swaps in the
2-layer CPU smoke variant of each config (``ModelConfig.reduced``) —
the size for CPU runs and tests; width never depends on the backend::

  PYTHONPATH=src python -m repro.launch.serve --reduced --queries 6

``main(argv)`` returns the ``RuntimeReport`` (with each engine's counters
under ``stats["edge_engine"]`` / ``stats["cloud_engine"]``), so scripts
drive the launcher's own code path.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import (ARCH_IDS, get_config, PAPER_EDGE_ARCH,
                           PAPER_CLOUD_ARCH)
from repro.core.hybridflow import HybridFlowPolicy
from repro.core.planner import SyntheticPlanner
from repro.core.profiler import train_default_router
from repro.core.exposure import mean_exposure
from repro.data.tasks import gen_benchmark, WorldModel
from repro.models import kvcache as KV
from repro.models import model as M
from repro.serving import (AutoscalePolicy, ServingConfig, ServingRuntime,
                           Trace)
from repro.serving.engine import ServingEngine, JAXExecutor

REDUCED_MAX_LEN = 192
FULL_MAX_LEN = 2048


def build_engines(edge_arch: str, cloud_arch: str, *, reduced: bool,
                  max_len: Optional[int] = None, prefill_chunk: int = 64,
                  prefix_reuse: bool = True,
                  prefix_block: int = KV.PREFIX_BLOCK):
    """The (edge, cloud) serving engines, 2 and 4 KV slots, with weights
    drawn from fixed seeds (0 and 1). Published widths unless
    ``reduced``; params and cache in the config's dtype either way.
    ``max_len`` defaults by size: 2048 lines per slot, 192 reduced."""
    max_len = max_len or (REDUCED_MAX_LEN if reduced else FULL_MAX_LEN)

    def engine(arch, seed, slots):
        cfg = get_config(arch)
        if reduced:
            cfg = cfg.reduced()
        # one jitted init: a single compile (which the persistent cache
        # keeps) instead of one small eager compile per parameter shape
        params = jax.jit(M.init_params, static_argnums=0)(
            cfg, jax.random.PRNGKey(seed))
        return ServingEngine(cfg, params, batch_slots=slots, max_len=max_len,
                             dtype=jnp.dtype(cfg.dtype),
                             prefill_chunk=prefill_chunk,
                             prefix_reuse=prefix_reuse,
                             prefix_block=prefix_block)
    return engine(edge_arch, 0, 2), engine(cloud_arch, 1, 4)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--edge-arch", default=PAPER_EDGE_ARCH, choices=ARCH_IDS)
    ap.add_argument("--cloud-arch", default=PAPER_CLOUD_ARCH, choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the 2-layer CPU smoke variant of each "
                         "config instead of its published widths")
    ap.add_argument("--max-len", type=int, default=None,
                    help=f"KV lines per slot (default {FULL_MAX_LEN}; "
                         f"{REDUCED_MAX_LEN} with --reduced)")
    ap.add_argument("--queries", type=int, default=6,
                    help="closed-loop batch size (open loop: trace decides)")
    ap.add_argument("--benchmark", default="gpqa")
    ap.add_argument("--tau0", type=float, default=0.35)
    ap.add_argument("--k-max", type=float, default=0.04)
    ap.add_argument("--max-inflight", type=int, default=8,
                    help="concurrently admitted queries (fleet admission)")
    ap.add_argument("--cloud-replicas", type=int, default=1,
                    help="shard the cloud engine across R pool replicas "
                         "(shared params, independent KV slot pools); "
                         "cloud concurrency becomes replicas x slots")
    ap.add_argument("--global-k-max", type=float, default=None,
                    help="fleet-wide API $ cap; forces edge when exhausted")
    ap.add_argument("--sequential", action="store_true",
                    help="seed-style one-query-at-a-time baseline")
    ap.add_argument("--no-pump", action="store_true",
                    help="synchronous per-subtask dispatch (pre-pump "
                         "baseline; engines never co-batch queries)")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prefill chunk length (long prompts never stall "
                         "co-resident decodes)")
    ap.add_argument("--no-prefix-reuse", action="store_true",
                    help="disable cross-request KV prefix reuse (on by "
                         "default: shared block-aligned prompt prefixes "
                         "seed new slots instead of re-prefilling)")
    ap.add_argument("--prefix-block", type=int, default=None,
                    help="prefix-hash block size in tokens (default: "
                         "kvcache.PREFIX_BLOCK)")
    ap.add_argument("--calibrate", action="store_true",
                    help="enable the LinUCB calibration head")

    traffic = ap.add_argument_group(
        "open-loop traffic", "timed admission against an arrival trace; "
        "the report adds TTFT / queue-wait percentiles at measured RPS")
    traffic.add_argument("--rps", type=float, default=None,
                         help="offered load: seeded Poisson arrivals at "
                              "this rate (queries/s)")
    traffic.add_argument("--duration", type=float, default=15.0,
                         help="trace horizon in seconds (with --rps)")
    traffic.add_argument("--trace", default=None, metavar="PATH",
                         help="replay a recorded Trace JSON "
                              "(overrides --rps)")
    traffic.add_argument("--trace-seed", type=int, default=0,
                         help="arrival-sampling seed (with --rps)")

    elastic = ap.add_argument_group(
        "elastic cloud pool", "occupancy-driven autoscaling of the cloud "
        "EnginePool (use with --cloud-replicas R)")
    elastic.add_argument("--autoscale", action="store_true",
                         help="grow/shrink replicas from live occupancy "
                              "with a modeled cold start; scale-to-zero "
                              "on gaps, poke-to-warm on the next arrival")
    elastic.add_argument("--min-replicas", type=int, default=0,
                         help="floor kept warm (0 enables scale-to-zero)")
    elastic.add_argument("--idle-to-zero", type=float, default=1.0,
                         help="idle seconds before scaling to zero")

    chaos = ap.add_argument_group(
        "chaos / recovery", "seeded fault injection and the retry policy "
        "that absorbs it")
    chaos.add_argument("--faults", default=None, metavar="SPEC",
                       help="seeded chaos spec, e.g. "
                            "'submit_fail=0.1,stall=0.05@0.3,crash=1@20,"
                            "slow=0:4,seed=3' (see serving.faults)")
    chaos.add_argument("--max-retries", type=int, default=2,
                       help="attempts per side before a cloud subtask "
                            "degrades to the edge")
    chaos.add_argument("--timeout", type=float, default=None,
                       help="per-attempt deadline in seconds")
    chaos.add_argument("--backoff-base", type=float, default=0.05,
                       help="base of the capped exponential retry backoff")
    args = ap.parse_args(argv)

    trace = None
    if args.trace is not None:
        trace = Trace.from_json(args.trace)
    elif args.rps is not None:
        trace = Trace.poisson(args.rps, args.duration, seed=args.trace_seed)
    if trace is not None and args.sequential:
        ap.error("--sequential is closed-loop; drop --rps/--trace")

    wm = WorldModel()
    edge_engine, cloud_engine = build_engines(
        args.edge_arch, args.cloud_arch, reduced=args.reduced,
        max_len=args.max_len, prefill_chunk=args.prefill_chunk,
        prefix_reuse=not args.no_prefix_reuse,
        prefix_block=args.prefix_block or KV.PREFIX_BLOCK)
    edge = JAXExecutor(edge_engine, wm, cloud=False, concurrency=1)
    # concurrency derives from engine capacity; with --cloud-replicas the
    # runtime scales this executor out to an EnginePool (replicas x slots)
    cloud = JAXExecutor(cloud_engine, wm, cloud=True, price_out=3.2e-5)

    print("warm-starting router from offline profiling...")
    router, info = train_default_router(n_queries=120, epochs=60)
    calibrator = None
    if args.calibrate:
        from repro.core.bandit import LinUCBCalibrator
        calibrator = LinUCBCalibrator(dim=3)
    policy = HybridFlowPolicy(router, tau0=args.tau0, k_max=args.k_max,
                              calibrator=calibrator, wm=wm)
    retry = None
    if args.faults is not None or args.timeout is not None:
        from repro.core.scheduler import RetryPolicy
        retry = RetryPolicy(max_retries=args.max_retries,
                            backoff_base=args.backoff_base,
                            timeout_s=args.timeout)
    autoscale = None
    if args.autoscale:
        autoscale = AutoscalePolicy(min_replicas=args.min_replicas,
                                    idle_to_zero_s=args.idle_to_zero)
    config = ServingConfig(max_inflight=args.max_inflight,
                           global_k_max=args.global_k_max,
                           pump=False if args.no_pump else None,
                           replicas=args.cloud_replicas,
                           autoscale=autoscale,
                           retry=retry, faults=args.faults)
    runtime = ServingRuntime(edge, cloud, policy, planner=SyntheticPlanner(),
                             config=config)

    n_queries = trace.n if trace is not None else args.queries
    qs = gen_benchmark(args.benchmark, n_queries)
    t0 = time.time()
    if trace is not None:
        print(f"open loop: {trace.describe()}")
        report = runtime.serve_trace(trace, qs)
        mode = f"open-loop(max_inflight={args.max_inflight})"
    else:
        report = runtime.serve(
            qs, mode="sequential" if args.sequential else "fleet")
        mode = "sequential" if args.sequential else \
            (f"{'sync' if args.no_pump else 'pumped'}"
             f"(max_inflight={args.max_inflight})")
    for q, res in zip(qs, report.results):
        route = "".join("C" if res.offload[s] else "e"
                        for s in sorted(res.offload))
        print(f"  {q.qid:14s} {res.plan_status:8s} route={route:8s} "
              f"correct={res.final_correct} wall={res.latency:5.2f}s "
              f"api=${res.api_cost:.4f}")
    _, nbar = mean_exposure(report.results)
    print(f"\n[{mode}] {report.summary()} | exposure Ē={nbar:.2f} | "
          f"real {time.time()-t0:.1f}s")
    if report.stats.get("forced_edge"):
        print(f"global budget forced {report.stats['forced_edge']} "
              f"subtasks onto the edge")
    if trace is not None and "autoscale" in (report.trace or {}):
        a = report.trace["autoscale"]
        print(f"autoscale: ups={a['scale_ups']} downs={a['scale_downs']} "
              f"to_zero={a['scale_to_zero']} pokes={a['pokes']}")
        for t, action, i in a["events"]:
            print(f"  t={t:7.3f}s {action:8s} replica {i}")
    if args.faults is not None:
        s = report.stats
        print(f"chaos: injected={s.get('injected')} | recovery: "
              f"retries={s.get('retries', 0)} "
              f"timeouts={s.get('timeouts', 0)} "
              f"degraded={s.get('degraded', 0)} | pool: "
              f"deaths={s.get('cloud_deaths', 0)} "
              f"failovers={s.get('cloud_failovers', 0)} "
              f"hedges={s.get('cloud_hedges', 0)} "
              f"health={s.get('cloud_replica_health')}")
        n_ret = sum(r.n_retries for r in report.results)
        n_deg = sum(r.n_degraded for r in report.results)
        print(f"per-query recovery: {n_ret} retried attempts, "
              f"{n_deg} degraded subtasks, 0 failed queries")
    cloud_eng = runtime.cloud.engine     # EnginePool when replicas > 1
    hits = (edge_engine.stats["prefix_hits"]
            + cloud_eng.stats.get("prefix_hits", 0))
    saved = (edge_engine.stats["prefill_tokens_saved"]
             + cloud_eng.stats.get("prefill_tokens_saved", 0))
    if not args.no_prefix_reuse:
        print(f"prefix reuse: {hits} hits, {saved} prefill tokens skipped")
    print(f"edge: {edge_engine.stats} | cloud: {cloud_eng.stats}")
    if hasattr(cloud_eng, "occupancy"):
        for o in cloud_eng.occupancy():
            life = f" {o['lifecycle']}" if "lifecycle" in o else ""
            print(f"  cloud replica {o['replica']}:{life} "
                  f"requests={o['requests']} "
                  f"peak_active={o['peak_active']}/{o['slots']} "
                  f"slot_reuses={o['slot_reuses']}")
    report.stats["edge_engine"] = dict(edge_engine.stats)
    report.stats["cloud_engine"] = dict(cloud_eng.stats)
    return report


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
