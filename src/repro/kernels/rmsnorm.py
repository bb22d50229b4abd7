"""RMSNorm Pallas TPU kernel.

Row-tiled: grid (N/BN,); each program normalizes a [BN, D] block in VMEM
(f32 accumulation, cast back to the input dtype). Memory-bound by design
— the point of the kernel is a single HBM round-trip with fused scale.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_2d(x, scale, *, eps: float = 1e-5, bn: int = 256,
               interpret: Optional[bool] = None):
    """x [N, D], scale [D] -> [N, D]."""
    N, D = x.shape
    bn = min(bn, max(N, 8))
    pn = (-N) % bn
    if pn:
        x = jnp.pad(x, ((0, pn), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((N + pn) // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pn, D), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, scale)
    return out[:N]
