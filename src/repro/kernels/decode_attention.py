"""Per-head decode-attention Pallas TPU kernel (flash-decode over a long
KV cache).

One query token per request attends over a [B, M, KV, hd] cache with a
per-request valid length. Grid (B, H, M/BK): KV blocks stream through
VMEM sequentially with online-softmax scratch, so the VMEM working set is
O(BK·hd) regardless of context length.

This is the original per-head kernel, kept as the simple reference shape
for roofline comparisons; the serving engine dispatches the batched
sibling (``batched_decode_attention``) which covers the whole GQA head
stack of every slot in a (B, M/BK) grid — B*H fewer grid steps per
decode tick.

The q row (1 x hd) is padded to an 8-row sublane tile; masking keeps the
math exact. kv_len rides in SMEM via PrefetchScalarGridSpec. ``bk`` is
clamped to the cache width M (and non-multiple tails are padded and
masked), so a small-cache config neither fails to tile nor over-reads —
the default bk=512 is a cap, not a requirement.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret

NEG_INF = -1e30
_QROWS = 8  # sublane padding for the single query row


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, bk: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # [_QROWS, hd]
    k = k_ref[0, 0].astype(jnp.float32)             # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (_QROWS, bk), 1)
    mask = kpos < kv_len_ref[b]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def decode_attention_bhmd(q, k, v, kv_len, *, bk: int = 512,
                          interpret: Optional[bool] = None):
    """q [B,H,hd]; k/v [B,KV,M,hd]; kv_len [B] -> o [B,H,hd]."""
    B, H, hd = q.shape
    KV, M = k.shape[1], k.shape[2]
    g = H // KV
    bk = min(bk, max(M, 8))
    pk = (-M) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nk = (M + pk) // bk
    qp = jnp.broadcast_to(q[:, :, None, :], (B, H, _QROWS, hd))

    kernel = functools.partial(_decode_kernel, scale=1.0 / math.sqrt(hd), bk=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec((1, 1, _QROWS, hd), lambda b, h, j, kv_len: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, kv_len, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, kv_len, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, _QROWS, hd),
                               lambda b, h, j, kv_len: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_QROWS,), jnp.float32),
            pltpu.VMEM((_QROWS,), jnp.float32),
            pltpu.VMEM((_QROWS, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, _QROWS, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(kv_len, jnp.int32), qp, k, v)
    return out[:, :, 0]
