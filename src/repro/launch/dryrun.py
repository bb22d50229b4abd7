import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, with no device allocation (ShapeDtypeStruct
inputs). Proves the sharding config is coherent and extracts the roofline
terms (memory_analysis + cost_analysis + collective bytes from the
post-SPMD HLO).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh single --out benchmarks/artifacts/dryrun
  (REPRO_DRYRUN_DEVICES=8 + --mesh-shape 2x4 for CPU-cheap smoke runs)
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, InputShape, ModelConfig
from repro.launch import mesh as MESH
from repro.distributed import sharding as SH
from repro.models import model as M
from repro.training.optimizer import AdamWState
from repro.training.loop import TrainConfig, make_train_step


# --------------------------------------------------------------------------
# HLO collective accounting
# --------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer bytes per collective op from post-SPMD HLO.

    Post-partitioning shapes are per-device, so the totals approximate the
    per-chip bytes entering the interconnect (ring all-gather/reduce move
    ~2(n-1)/n x this; we report the raw buffer totals and keep the factor
    out of the roofline term — documented in EXPERIMENTS.md).
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for op in _COLLECTIVES:
            if f" {op}(" in line or f" {op}-start(" in line:
                head = line.split(f" {op}", 1)[0]
                rhs = head.split("=", 1)[-1]
                for dt, dims in _SHAPE_RE.findall(rhs):
                    if dt in _DTYPE_BYTES:
                        out[op] += _shape_bytes(dt, dims)
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _mem_dict(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    out["total_bytes"] = (out.get("argument_size_in_bytes", 0)
                          + out.get("output_size_in_bytes", 0)
                          + out.get("temp_size_in_bytes", 0)
                          - out.get("alias_size_in_bytes", 0))
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "transcendentals": float(ca.get("transcendentals", 0.0))}


# --------------------------------------------------------------------------
# per-combination lowering
# --------------------------------------------------------------------------

def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))


def _abstract_opt(params):
    dt = jnp.bfloat16 if _FLAGS["opt_bf16"] else jnp.float32
    def mk():
        zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, dt), params)
        return AdamWState(step=jnp.zeros((), jnp.int32), mu=zeros, nu=zeros)
    return jax.eval_shape(mk)


def lower_combo(cfg: ModelConfig, shape: InputShape, mesh, *,
                remat: bool = False, remat_policy: Optional[str] = None,
                donate: bool = True, strategy: str = "tp"):
    """Returns (lowered, in_shardings_info). Raises on sharding errors."""
    from repro.distributed.context import set_mesh
    set_mesh(mesh)  # shard_map layers (ep MoE) read the ambient mesh
    params = _abstract_params(cfg)
    p_sh = SH.param_shardings(cfg, params, mesh, strategy=strategy)

    if shape.kind == "train":
        specs = M.input_specs(cfg, shape)
        b_sh = SH.batch_shardings(cfg, specs, mesh)
        opt = _abstract_opt(params)
        o_sh = AdamWState(step=SH.replicated(mesh),
                          mu=jax.tree.map(lambda s: s, p_sh),
                          nu=jax.tree.map(lambda s: s, p_sh))
        tcfg = TrainConfig(remat=remat, remat_policy=remat_policy)
        step = make_train_step(cfg, tcfg)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         donate_argnums=(0, 1) if donate else ())
        with mesh:
            return jitted.lower(params, opt, specs)

    if shape.kind == "prefill":
        specs = M.input_specs(cfg, shape)
        b_sh = SH.batch_shardings(cfg, specs["batch"], mesh)
        c_sh = SH.cache_shardings(cfg, specs["cache"], mesh)

        def prefill(p, b, c):
            return M.serve_prefill(p, cfg, b, c)

        jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh, c_sh),
                         donate_argnums=(2,) if donate else ())
        with mesh:
            return jitted.lower(params, specs["batch"], specs["cache"])

    # decode
    cache_dt = jnp.float8_e4m3fn if _FLAGS["kv_f8"] else jnp.bfloat16
    specs = M.input_specs(cfg, shape, cache_dtype=cache_dt)
    t_sh, pos_sh = SH.token_shardings(shape.global_batch, mesh)
    c_sh = SH.cache_shardings(cfg, specs["cache"], mesh)

    def decode(p, t, pos, c):
        return M.serve_decode(p, cfg, t, pos, c)

    jitted = jax.jit(decode, in_shardings=(p_sh, t_sh, pos_sh, c_sh),
                     donate_argnums=(3,) if donate else ())
    with mesh:
        return jitted.lower(params, specs["token"], specs["pos"],
                            specs["cache"])


def applicable(cfg: ModelConfig, shape: InputShape) -> bool:
    """long_500k only for sub-quadratic archs (DESIGN.md §4)."""
    if shape.name == "long_500k":
        return cfg.supports_long_context
    return True


def _depth_variants(cfg: ModelConfig):
    """Two shallow copies of the config for per-layer cost extrapolation.

    XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, so the
    full-depth module under-reports flops/bytes/collectives by ~L×. We
    lower the same (shape, mesh) at two shallow depths and extrapolate
    linearly: cost(L) = c_a + (c_b - c_a) · (L - L_a)/(L_b - L_a).
    Depths respect each family's block-group granularity.
    """
    if cfg.family == "hybrid":
        a, b = cfg.attn_every, 2 * cfg.attn_every
    elif cfg.family == "ssm":
        g = cfg.mlstm_per_slstm + 1
        a, b = g, 2 * g
    else:
        a, b = 2, 4
    kw_a, kw_b = {"n_layers": a}, {"n_layers": b}
    if cfg.is_encoder_decoder:
        kw_a["n_encoder_layers"] = a
        kw_b["n_encoder_layers"] = b
    return (cfg.variant(**kw_a), a), (cfg.variant(**kw_b), b)


def _extrapolate(v_a: float, v_b: float, la: int, lb: int, L: int) -> float:
    return v_a + (v_b - v_a) * (L - la) / (lb - la)


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D forward (N = active params)."""
    n = cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per request


# §Perf optimization bundles selectable via --opt (comma-separated)
_OPTS = {
    "blocked_attn": dict(attention_block_q=512),
    "blocked_attn_2k": dict(attention_block_q=2048),
    "shard_seq": dict(shard_attn_seq=True),
    "ep_moe": dict(moe_impl="ep"),
    "expand_kv": "EXPAND_KV",     # resolved per-config (needs mesh size)
    "fsdp": "FSDP",               # strategy, not a config field
    "opt_bf16": "OPT_BF16",       # bf16 Adam moments (halves optimizer HBM)
    "kv_f8": "KV_F8",             # fp8(e4m3) KV cache (halves cache reads)
}

_FLAGS = {"opt_bf16": False, "kv_f8": False}


def apply_opts(cfg: ModelConfig, opts) -> tuple:
    """Returns (cfg, strategy) with the requested §Perf knobs applied."""
    strategy = "tp"
    kw = {}
    _FLAGS["opt_bf16"] = False
    _FLAGS["kv_f8"] = False
    for o in opts or ():
        v = _OPTS[o]
        if v == "FSDP":
            strategy = "fsdp"
        elif v == "OPT_BF16":
            _FLAGS["opt_bf16"] = True
        elif v == "KV_F8":
            _FLAGS["kv_f8"] = True
        elif v == "EXPAND_KV":
            if cfg.uses_attention and cfg.n_heads % 16 == 0 \
                    and cfg.n_kv_heads < 16 and 16 % cfg.n_kv_heads == 0:
                kw["kv_cache_expand_heads"] = 16
        else:
            kw.update(v)
    return (cfg.variant(**kw) if kw else cfg), strategy


def run_combo(arch: str, shape_name: str, *, multi_pod: bool,
              mesh_shape: Optional[tuple] = None,
              remat: Optional[bool] = None, remat_policy: Optional[str] = None,
              extrapolate: bool = True, opts=()) -> Dict[str, Any]:
    cfg = get_config(arch)
    cfg, strategy = apply_opts(cfg, opts)
    shape = SHAPES[shape_name]
    if remat is None:
        remat = shape.kind == "train"  # full activation remat is the
        #                                baseline policy for training
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "family": cfg.family,
        "params": cfg.param_count(),
        "active_params": cfg.param_count(active_only=True),
        "model_flops": model_flops(cfg, shape),
        "remat": remat, "remat_policy": remat_policy,
        "opts": list(opts), "strategy": strategy,
    }
    if not applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = "full attention at 524k is quadratic; no SWA variant"
        return rec
    if mesh_shape is not None:
        axes = ("pod", "data", "model")[-len(mesh_shape):]
        mesh = jax.make_mesh(mesh_shape, axes)
    else:
        mesh = MESH.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered = lower_combo(cfg, shape, mesh, remat=remat,
                          remat_policy=remat_policy, strategy=strategy)
    rec["lower_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)
    rec["memory"] = _mem_dict(compiled)
    rec["cost"] = _cost_dict(compiled)
    rec["collectives"] = collective_bytes(compiled.as_text())
    rec["n_devices"] = int(mesh.devices.size)

    if extrapolate:
        from repro.models import transformer as T
        try:
            (cfg_a, la), (cfg_b, lb) = _depth_variants(cfg)
            recs = []
            T.set_scan_unroll(True)  # exact per-layer costs (see layer_scan)
            for cv, lv in ((cfg_a, la), (cfg_b, lb)):
                cl = lower_combo(cv, shape, mesh, remat=remat,
                                 remat_policy=remat_policy,
                                 strategy=strategy).compile()
                recs.append({"n_layers": lv, "cost": _cost_dict(cl),
                             "collectives": collective_bytes(cl.as_text())})
            L = cfg.n_layers
            rec["depth_variants"] = recs
            rec["cost_extrapolated"] = {
                k: _extrapolate(recs[0]["cost"][k], recs[1]["cost"][k],
                                la, lb, L)
                for k in recs[0]["cost"]}
            rec["collectives_extrapolated"] = {
                k: _extrapolate(recs[0]["collectives"][k],
                                recs[1]["collectives"][k], la, lb, L)
                for k in recs[0]["collectives"]}
        except Exception as e:  # extrapolation is best-effort
            rec["extrapolation_error"] = repr(e)
        finally:
            T.set_scan_unroll(False)

    rec["status"] = "ok"
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--mesh-shape", default=None,
                    help="override, e.g. 2x4 (smoke tests)")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    ap.add_argument("--remat", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--no-extrapolate", action="store_true")
    ap.add_argument("--opt", default="",
                    help="comma-separated §Perf knobs: " + ",".join(_OPTS))
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)
    remat = {"auto": None, "on": True, "off": False}[args.remat]

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    mesh_shape = (tuple(int(x) for x in args.mesh_shape.split("x"))
                  if args.mesh_shape else None)
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                try:
                    rec = run_combo(arch, shape, multi_pod=mp,
                                    mesh_shape=mesh_shape, remat=remat,
                                    remat_policy=args.remat_policy,
                                    extrapolate=not args.no_extrapolate,
                                    opts=[o for o in args.opt.split(",") if o])
                except Exception as e:  # noqa
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if mp else "single",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                    n_fail += 1
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                st = rec["status"]
                extra = ""
                if st == "ok":
                    gb = rec["memory"]["total_bytes"] / 2**30
                    extra = (f" mem/dev={gb:.2f}GiB flops={rec['cost']['flops']:.3e}"
                             f" coll={rec['collectives']['total']/2**20:.1f}MiB"
                             f" ({rec['lower_s']}+{rec['compile_s']}s)")
                elif st == "error":
                    extra = " " + rec["error"][:120]
                print(f"[{st}] {tag}{extra}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
