"""Smoke run of the serving main path on one TPU chip.

    python chip_smoke.py

Everything runs in this one process, which owns the chip:

1. Device check: the JAX backend must be ``tpu``; on any other platform
   the script exits non-zero naming what it found, before anything else.
2. Phase ``serve``: ``repro.launch.serve.main`` serves 6 gpqa queries
   closed loop with a qwen2-1.5b edge engine and a 2-replica qwen2-1.5b
   cloud pool on the one chip, at published widths (28 layers, d_model
   1536, 12 heads, GQA kv=2, vocab 151,936), bfloat16 weights (random,
   from fixed seeds) and KV cache, 2048 KV lines per slot. Every query
   must complete with tokens out, and every recovery counter must be 0:
   the scheduler and the pool absorb exceptions by design, so a non-zero
   count here is a device error that was masked.
3. Phase ``pallas``: the same fleet with the Pallas kernels dispatched,
   which must be compiled (not interpreted); then the ragged-prefill and
   batched-decode kernels are checked against their ``layers`` reference
   twins on seeded bf16 inputs at serving shapes.

Per-phase wall times (compilation included; split into set-up — router
warm-start, weight init, engine construction — and the serving loop),
compile counts, prefix hits and peak device memory are printed as smoke
facts, not measurements. The
last line of output is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

ARCH = "qwen2-1.5b"
QUERIES = 6
MAX_LEN = 2048
RECOVERY_COUNTERS = ("retries", "degraded", "exec_faults", "cloud_deaths",
                     "cloud_failovers")
BF16_TOL = 2e-2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check_device():
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        fail(f"needs a TPU backend, found platform {backend!r} "
             f"({jax.devices()[0].device_kind})")
    return jax.devices()


def versions() -> dict:
    from importlib import metadata
    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = metadata.version(dist)
        except metadata.PackageNotFoundError:
            out[dist] = "not installed"
    return out


def peak_bytes(dev) -> object:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def serve_phase(name: str, dev) -> dict:
    from repro.launch import serve
    t0 = time.perf_counter()
    report = serve.main([
        "--edge-arch", ARCH, "--cloud-arch", ARCH,
        "--max-len", str(MAX_LEN), "--cloud-replicas", "2",
        "--queries", str(QUERIES), "--benchmark", "gpqa"])
    wall = time.perf_counter() - t0
    stats = report.stats
    if len(report.results) != QUERIES:
        fail(f"{name}: {len(report.results)} of {QUERIES} queries completed")
    for res in report.results:
        missing = set(res.dag.sids) - set(res.results)
        if missing:
            fail(f"{name}: {res.qid} has no result for subtasks "
                 f"{sorted(missing)}")
        empty = [s for s, r in res.results.items() if r.tok_out <= 0]
        if empty:
            fail(f"{name}: {res.qid} subtasks {empty} produced no tokens")
    nonzero = {k: stats.get(k) for k in RECOVERY_COUNTERS
               if stats.get(k) != 0}
    if nonzero:
        fail(f"{name}: recovery counters not 0 (a masked device error): "
             f"{nonzero}; replica errors: "
             f"{stats.get('cloud_replica_errors')}")
    edge, cloud = stats["edge_engine"], stats["cloud_engine"]
    facts = {
        "phase": name, "wall_s_incl_compile": wall,
        "setup_s": wall - report.wall_s, "serve_loop_s": report.wall_s,
        "queries": len(report.results),
        "tokens_out": edge["tokens_out"] + cloud["tokens_out"],
        "jit_retraces": max(edge["jit_retraces"], cloud["jit_retraces"]),
        "prefix_hits": edge["prefix_hits"] + cloud["prefix_hits"],
        "prefill_backend": [edge["prefill_backend"],
                            cloud["prefill_backend"]],
        "peak_bytes_in_use": peak_bytes(dev),
    }
    print("smoke " + json.dumps(facts), flush=True)
    return facts


def kernel_checks() -> None:
    """Pallas kernels vs their ``layers`` reference twins at serving
    shapes (qwen2-1.5b heads), seeded bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models import layers as L

    H, KV, hd = 12, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    def normal(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    def max_err(got, want):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not np.isfinite(got).all():
            return float("inf")
        return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))

    G, S, W = 4, 64, 1024
    q = normal(ks[0], (G, S, H, hd))
    k = normal(ks[1], (G, W, KV, hd))
    v = normal(ks[2], (G, W, KV, hd))
    pos0 = jax.random.randint(ks[3], (G,), 0, W - S + 1).astype(jnp.int32)
    take = jax.random.randint(ks[4], (G,), 1, S + 1).astype(jnp.int32)
    got = ops.ragged_prefill_attention(q, k, v, pos0, take)
    want = L.ragged_prefill_attention(q, k, v, pos0=pos0, take=take)
    err_prefill = max_err(got, want)

    B, M = 4, 2048
    q = normal(ks[5], (B, 1, H, hd))
    k = normal(ks[6], (B, M, KV, hd))
    v = normal(ks[7], (B, M, KV, hd))
    kv_len = jnp.asarray([1, 700, 1500, M], jnp.int32)
    got = ops.decode_attention(q, k, v, kv_len=kv_len)
    want = L.attention(q, k, v, causal=False, kv_len=kv_len)
    err_decode = max_err(got, want)

    print("smoke " + json.dumps({
        "phase": "kernels", "ragged_prefill_max_err": err_prefill,
        "batched_decode_max_err": err_decode, "tol": BF16_TOL}), flush=True)
    if not err_prefill <= BF16_TOL:
        fail(f"ragged prefill kernel differs from its reference twin: "
             f"{err_prefill} > {BF16_TOL}")
    if not err_decode <= BF16_TOL:
        fail(f"batched decode kernel differs from its reference twin: "
             f"{err_decode} > {BF16_TOL}")


def main() -> None:
    t_start = time.perf_counter()
    devices = check_device()
    dev = devices[0]
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.kernels import dispatch
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    print("smoke " + json.dumps({
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(devices), **versions(),
        "compile_cache_dir": cache_dir}), flush=True)

    serve_phase("serve", dev)
    gc.collect()

    with dispatch.pallas_enabled(True):
        if dispatch.interpret_mode():
            fail("Pallas kernels would run in interpret mode on the TPU")
        facts = serve_phase("pallas", dev)
        if facts["prefill_backend"] != ["pallas", "pallas"]:
            fail(f"prefill did not run the ragged Pallas kernel: "
                 f"{facts['prefill_backend']}")
        kernel_checks()

    print("smoke " + json.dumps({
        "phase": "total", "wall_s": time.perf_counter() - t_start}),
        flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
