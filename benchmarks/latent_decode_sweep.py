"""Latent decode attention on the chip: the XLA path against the Pallas
kernel at several block sizes, at deepseek-v2-lite's published widths.

    python3 -m benchmarks.latent_decode_sweep [--slots 32] [--iters 20]

One program per variant runs a decode step's 27 layers of
``mla.decode_attend`` over a stacked bf16 pool of ``slots`` x 4,096
lines (each layer writes every row's line and attends), with the rows'
live lengths drawn uniformly from 64-2,047 (mean ~1,055, about the
token-weighted mean context of the decode-reasoning traffic), then with
every row full. Prints one JSON line per variant: milliseconds per
27-layer pass (host clock around ``block_until_ready``, after a warm-up),
the live bytes over that time as a share of 819 GB/s, and the largest
gap to the XLA path's output. Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.kernels import dispatch as kd
from repro.models import mla as MLA

HBM = 819e9


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--blocks", default="256,512,1024")
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("needs a TPU")
    cfg = get_config("deepseek-v2-lite")
    B, M, L = a.slots, 4096, cfg.n_layers
    H, N, R, C = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.kv_lora_rank)
    bf = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    p = MLA.init_mla(ks[0], cfg, dtype=bf)
    ckv = jax.random.normal(ks[1], (L, B, M, C), bf)
    kpe = jax.random.normal(ks[2], (L, B, R, M), bf)
    q_nope = jax.random.normal(ks[3], (L, B, 1, H, N), bf)
    q_pe = jax.random.normal(ks[4], (L, B, 1, H, R), bf)
    c_new = jax.random.normal(ks[5], (L, B, 1, C), bf)
    k_new = jax.random.normal(ks[6], (L, B, 1, R), bf)
    spread = np.random.default_rng(0).integers(64, 2048, B).astype(np.int32)

    def program(bk):
        def step(ckv, kpe, pos):
            kvl = pos + 1

            def layer(i, carry):
                ckv, kpe, acc = carry
                if bk is None:
                    out, ckv, kpe = MLA.decode_attend(
                        p, cfg, q_nope[i], q_pe[i], c_new[i], k_new[i], ckv,
                        kpe, i, pos, kvl)
                else:
                    ckv = ckv.at[i, jnp.arange(B), pos].set(c_new[i][:, 0])
                    out, kpe = MLA.attend_absorbed_kernel(
                        p, cfg, q_nope[i], q_pe[i], k_new[i], ckv, kpe, i,
                        kvl, bk=bk)
                return ckv, kpe, acc + out.astype(jnp.float32)

            return jax.lax.fori_loop(
                0, L, layer, (ckv, kpe, jnp.zeros((B, 1, H * cfg.v_head_dim),
                                                  jnp.float32)))
        return jax.jit(step, donate_argnums=(0, 1))

    variants = [("xla", None)] + [(f"kernel_bk{b}", int(b))
                                  for b in a.blocks.split(",")]
    for lens_name, lens in (("spread", spread),
                            ("full", np.full(B, M, np.int32))):
        pos = jnp.asarray(lens - 1)
        live = int(np.sum(lens)) * L * (C + R) * 2
        want = None
        for name, bk in variants:
            real = kd.interpret_mode
            if bk is None:       # the reference path, on the chip
                kd.interpret_mode = lambda: True
            try:
                fn = program(bk)
                ckv, kpe, out = fn(ckv, kpe, pos)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(a.iters):
                    ckv, kpe, out = fn(ckv, kpe, pos)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) / a.iters * 1e3
            finally:
                kd.interpret_mode = real
            out = np.asarray(out)
            if want is None:
                want = out
            gap = float(np.max(np.abs(out - want)) / np.max(np.abs(want)))
            print(json.dumps({
                "variant": name, "lengths": lens_name, "slots": B,
                "ms_per_pass": round(ms, 4),
                "live_bytes_share_of_819GBps": round(
                    100 * live / HBM / (ms / 1e3), 2),
                "rel_gap_to_xla": gap,
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
