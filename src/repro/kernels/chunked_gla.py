"""Chunked gated linear recurrence Pallas TPU kernel.

The Mamba2 (SSD) / mLSTM compute hot spot: for per-(batch, head) scalar
decays a_t,

    S_t = a_t · S_{t-1} + k_t v_tᵀ ;  y_t = q_t · S_t

Grid (B, H, T/C): the chunk dimension is sequential ("arbitrary") and
carries the [Dk, Dv] state in VMEM scratch; each step does the intra-chunk
quadratic form (tri-masked decay attention — two MXU matmuls) plus the
inter-chunk state contribution, then advances the state. Mirrors
models/linear_recurrence.chunked_gla (the XLA path the models use) and is
validated against gla_reference in interpret mode.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret


# Ref order contract (checked statically by reprolint pallas-contract):
# no scalar prefetch — 4 in_specs, 1 out, 1 VMEM scratch, in order.
def _gla_kernel(q_ref, k_ref, v_ref, la_ref, o_ref, s_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # [C, Dk]
    k = k_ref[0, 0].astype(jnp.float32)          # [C, Dk]
    v = v_ref[0, 0].astype(jnp.float32)          # [C, Dv]
    la = la_ref[0, 0].astype(jnp.float32)        # [C] (padded lanes are 0)

    cum = jnp.cumsum(la)                          # within-chunk log decay
    total = cum[-1]

    # intra-chunk: y[i] = sum_{j<=i} exp(cum_i - cum_j) (q_i·k_j) v_j
    qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = iota_i >= iota_j
    decay = jnp.where(tri, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    y = jax.lax.dot(qk * decay, v, preferred_element_type=jnp.float32)

    # inter-chunk: y[i] += exp(cum_i) · q_i · S_prev
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot(
        q, s_scr[...], preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)

    # state update: S = exp(total)·S_prev + Σ_j exp(total - cum_j) k_j v_jᵀ
    kdec = k * jnp.exp(total - cum)[:, None]
    s_scr[...] = jnp.exp(total) * s_scr[...] + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def chunked_gla_bhtd(q, k, v, log_a, *, chunk: int = 128,
                     interpret: Optional[bool] = None):
    """q,k [B,H,T,Dk]; v [B,H,T,Dv]; log_a [B,H,T] -> y [B,H,T,Dv].

    T is padded to a chunk multiple with log_a=0, k=v=0 (identity steps).
    """
    B, H, T, Dk = q.shape
    Dv = v.shape[-1]
    C = min(chunk, max(T, 8))
    pt = (-T) % C
    if pt:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pt), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pt), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pt), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, 0), (0, pt)))
    nc = (T + pt) // C

    out = pl.pallas_call(
        functools.partial(_gla_kernel, chunk=C),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, Dk), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, Dv), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C), lambda b, h, c: (b, h, c)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, Dv), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T + pt, Dv), v.dtype),
        scratch_shapes=[pltpu.VMEM((Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, log_a)
    return out[:, :, :T]
