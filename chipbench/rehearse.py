"""Compile each cell's decode and prefill step programs for a described
TPU v5e, without the chip, and reckon whether weights, KV pool and the
largest step's temporaries fit on one chip.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse [--write] [workload ...]

For every engine shape of a cell it compiles the engine's decode program
and the widest prefill program the cell launches (as its entry's
``shapes(cell)`` says), prints ``memory_analysis()`` and, with
``--write``, stores the reckoning under ``sizing`` in the cell's file.
A compile that passes here is not a chip run.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

from chipbench import registry

GB = 1e9


def shapes_of(cell) -> list:
    """The engine shapes of a cell, as its entry ``drive_<entry>.py``
    gives them in ``shapes(cell)``: (label, slots, max_len, chunk,
    replicas, g, kv_width) each, where ``g`` and ``kv_width`` are the
    rows and cache lines of the widest prefill the cell launches. Each
    shape has weights of its own; replicas of one shape share them and
    hold a KV pool each."""
    entry = importlib.import_module(
        f"chipbench.drive_{cell.traffic['entry']}")
    return entry.shapes(cell)


def rehearse(cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.models import model as M
    from repro.serving.engine import _jit_steps

    one = SingleDeviceSharding(topo.devices[0])
    cfg = cell.family.served_config(cell.model)
    dt = jnp.dtype(cfg.dtype)

    def sds(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=dt)))
    wbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(params))
    i32, f32 = jnp.int32, jnp.float32
    key = sds(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    out = {"weights_bytes": int(wbytes), "engines": []}
    for label, slots, max_len, chunk, copies, g, kv in shapes_of(cell):
        cache = sds(jax.eval_shape(
            lambda: M.init_cache(cfg, slots, max_len, dtype=dt)))
        cbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))

        def a(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        decode_fn, prefill_fn = _jit_steps(cfg, max_len, False)
        dec = decode_fn.lower(params, a((slots, 1), i32), a((slots,), i32),
                              cache, key, a((slots,), f32),
                              a((slots,), i32)).compile()
        pre = prefill_fn.lower(params, a((g, chunk), i32), a((g,), i32),
                               a((g,), i32), a((g,), i32), a((slots,), i32),
                               cache, key, a((g,), f32), kv).compile()
        row = {"engine": label, "slots": slots, "max_len": max_len,
               "replicas": copies, "prefill_rows": g, "prefill_kv": kv,
               "kv_pool_bytes": int(cbytes)}
        for name, c in (("decode", dec), ("prefill", pre)):
            m = c.memory_analysis()
            row[name] = {"argument_bytes": int(m.argument_size_in_bytes),
                         "output_bytes": int(m.output_size_in_bytes),
                         "temp_bytes": int(m.temp_size_in_bytes),
                         "alias_bytes": int(m.alias_size_in_bytes)}
        out["engines"].append(row)
    pools = sum(r["kv_pool_bytes"] * r["replicas"] for r in out["engines"])
    n_weights = len(out["engines"])
    worst_temp = max(r[k]["temp_bytes"] for r in out["engines"]
                     for k in ("decode", "prefill"))
    out["reckoning_bytes"] = int(n_weights * wbytes + pools + worst_temp)
    out["reckoning"] = (f"{n_weights} x weights + every KV pool + the largest "
                        f"step's temporaries = "
                        f"{out['reckoning_bytes'] / GB:.2f} GB of the chip's 16")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.rehearse")
    ap.add_argument("workloads", nargs="*")
    ap.add_argument("--write", action="store_true",
                    help="store the reckoning in each cell's file")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(registry.ROOT / "src"))
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import dispatch
    dispatch.interpret_mode = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = args.workloads or [w["name"] for w in
                               registry.load_benchmark()["workloads"]]
    for name in names:
        cell = registry.load_cell(name)
        res = rehearse(cell, topo)
        print(json.dumps({"workload": name, **res}), flush=True)
        if args.write:
            path = registry.PKG / "cells" / f"{name}.json"
            data = json.loads(path.read_text())
            data["sizing"] = res
            path.write_text(json.dumps(data, indent=2) + "\n")


if __name__ == "__main__":
    main()
