"""Serve a HybridFlow deployment with REAL JAX executor models, many
queries in flight at once.

Quickstart
----------
Two serving engines (an 'edge' model and a 'cloud' model, built by the
serving launcher's ``build_engines``) execute subtasks
scheduled by the dependency-aware router. The multi-query runtime admits
every query up front; dispatch goes through the fleet scheduler's *async
pump loop*: every routed subtask is ``submit``-ed into its engine's
queue, the loop keeps stepping both engines while routing continues, and
co-scheduled subtasks from different queries decode in the same
micro-batches. Prefill is batched and chunked — all newly admitted slots
prefill in one padded call that writes KV lines straight into the shared
slot pool, and prompts longer than ``prefill_chunk`` advance one chunk
per step so they never stall co-resident decodes. Sampling happens on
device inside the jitted step (one host transfer of token ids per step).

    # pumped fleet serving (default: 8 queries in flight) at the CPU
    # smoke size; without --reduced both engines run at published widths
    PYTHONPATH=src python examples/serve_hybrid.py --reduced --queries 8

    # pre-pump synchronous dispatch (engines never co-batch queries)
    PYTHONPATH=src python examples/serve_hybrid.py --reduced --queries 8 \
        --no-pump

    # compare against the seed's one-query-at-a-time loop
    PYTHONPATH=src python examples/serve_hybrid.py --reduced --queries 8 \
        --sequential

    # cap fleet-wide API spend; exhaustion forces edge execution
    PYTHONPATH=src python examples/serve_hybrid.py --reduced \
        --global-k-max 0.01

    # shard the cloud engine across 2 pool replicas (shared params,
    # independent KV slot pools; cloud concurrency = replicas x slots)
    PYTHONPATH=src python examples/serve_hybrid.py --reduced \
        --cloud-replicas 2

The printed report includes fleet throughput, p50/p99 per-query
makespan, accuracy and API cost, plus the engines' counters —
``slot_reuses`` > 0 shows requests recycling the bounded cache pool,
``peak_active`` >= 2 shows genuine cross-query co-residency, and
``prefill_batch_max`` >= 2 shows the prefill planner batching admitted
requests into single calls.

Programmatic use mirrors the CLI::

    from repro.serving import ServingConfig, ServingRuntime
    rt = ServingRuntime(edge, cloud, policy, planner=planner,
                        config=ServingConfig(max_inflight=8))
    report = rt.serve(queries)   # or rt.serve(queries, mode="sequential")
    print(report.summary())
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import PAPER_EDGE_ARCH, PAPER_CLOUD_ARCH
from repro.core.hybridflow import HybridFlowPolicy
from repro.core.planner import SyntheticPlanner
from repro.core.profiler import train_default_router
from repro.data.tasks import gen_benchmark, WorldModel
from repro.launch.serve import build_engines
from repro.serving import ServingConfig, ServingRuntime
from repro.serving.engine import JAXExecutor


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer CPU smoke variant of each config")
    ap.add_argument("--queries", type=int, default=6)
    ap.add_argument("--edge-arch", default=PAPER_EDGE_ARCH)
    ap.add_argument("--cloud-arch", default=PAPER_CLOUD_ARCH)
    ap.add_argument("--max-inflight", type=int, default=8)
    ap.add_argument("--cloud-replicas", type=int, default=1,
                    help="shard the cloud engine across R pool replicas")
    ap.add_argument("--global-k-max", type=float, default=None)
    ap.add_argument("--sequential", action="store_true")
    ap.add_argument("--no-pump", action="store_true",
                    help="synchronous per-subtask dispatch (pre-pump "
                         "baseline)")
    args = ap.parse_args()

    size = "reduced" if args.reduced else "published widths"
    print(f"edge executor: {args.edge_arch}; cloud executor: "
          f"{args.cloud_arch} ({size})")
    wm = WorldModel()
    edge_engine, cloud_engine = build_engines(
        args.edge_arch, args.cloud_arch, reduced=args.reduced)
    edge = JAXExecutor(edge_engine, wm, cloud=False, concurrency=1)
    # concurrency derives from capacity; --cloud-replicas scales this
    # executor out to an EnginePool inside the runtime
    cloud = JAXExecutor(cloud_engine, wm, cloud=True, price_out=3.2e-5)

    router, _ = train_default_router(n_queries=100, epochs=60)
    policy = HybridFlowPolicy(router, wm=wm)
    config = ServingConfig(max_inflight=args.max_inflight,
                           global_k_max=args.global_k_max,
                           pump=False if args.no_pump else None,
                           replicas=args.cloud_replicas)
    runtime = ServingRuntime(edge, cloud, policy, planner=SyntheticPlanner(),
                             config=config)

    qs = gen_benchmark("gpqa", args.queries)
    t0 = time.time()
    report = runtime.serve(
        qs, mode="sequential" if args.sequential else "fleet")
    for q, res in zip(qs, report.results):
        routed = "".join("C" if res.offload[s] else "e"
                         for s in sorted(res.offload))
        print(f"  {q.qid:10s} plan={res.plan_status:8s} route={routed:8s} "
              f"correct={res.final_correct} wall={res.latency:.2f}s")
    mode = "sequential" if args.sequential else \
        (f"{'sync' if args.no_pump else 'pumped'}"
         f"(max_inflight={args.max_inflight})")
    print(f"\n[{mode}] {report.summary()} | real {time.time()-t0:.1f}s")
    print(f"edge engine: {edge_engine.stats}")
    cloud_eng = runtime.cloud.engine     # EnginePool when replicas > 1
    print(f"cloud engine: {cloud_eng.stats}")
    if hasattr(cloud_eng, "occupancy"):
        for o in cloud_eng.occupancy():
            print(f"  cloud replica {o['replica']}: "
                  f"requests={o['requests']} "
                  f"peak_active={o['peak_active']}/{o['slots']}")


if __name__ == "__main__":
    main()
