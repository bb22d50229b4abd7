"""Batched decode-attention Pallas TPU kernel: every serving slot in ONE
launch.

The serving engine's fused decode step advances all G KV slots by one
token per tick. Its attention is: one query token per slot over the
slot-pooled [B, KV, M, hd] cache, masked to each slot's valid length.
The original ``decode_attention_bhmd`` kernel already streamed KV blocks
but gridded (B, H, M/BK) — B*H tiny per-head steps per tick. This
sibling covers the whole batch's head stack in a (B, M/BK) grid:

* every block carries ALL H query heads, GQA-folded onto their KV head
  ([B,H,hd] -> [B, KV, grp, hd], padded up to an 8-row sublane tile) so
  the score and weighted-value contractions are each one KV-batched
  ``dot_general`` per block — one MXU issue for the whole slot's heads;
* per-slot ``kv_len`` rides in scalar-prefetch SMEM; the mask is
  ``kpos < kv_len[b]`` and, with a sliding ``window`` over a full
  (non-rolling) cache, ``kpos >= kv_len[b] - window``;
* KV blocks entirely past ``kv_len[b]`` (or below the window) are
  skipped via ``pl.when`` — a slot early in its generation pays
  O(kv_len), not O(M). ``kv_len == 0`` rows skip every block and emit
  exact zeros (the safe-denominator finish);
* the innermost KV walk is sequential ("arbitrary"): Mosaic's automatic
  pipeline double-buffers the next KV block's DMA against the current
  block's compute, with the q block resident across the walk.

Rolling-window caches already bound M to the window and track validity
via ``kv_len``, so the engine passes ``window=None``; the explicit
``window`` mask is for full caches (parity-tested in
``tests/test_batched_decode_kernel.py``).

Sampling is NOT part of this kernel — it fuses at the XLA level: the
engine's jitted decode step (``serving.engine._jit_steps``) runs
model-with-this-kernel + ``_device_sample`` in one compiled program, so
there is no separate host-visible sample op per token.
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
_SUBLANE = 8   # pad the folded [KV, grp, hd] q tile up to 8 sublane rows


# Ref order contract (checked statically by reprolint pallas-contract):
# 1 scalar-prefetch ref (kv_len), then in_specs, out, scratch — the
# signature arity must match the PrefetchScalarGridSpec below.
def _batched_decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale: float,
                           window: Optional[int], bk: int, gp: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = kv_len_ref[b]
    # dead-block skip: nothing valid at or past kv_len; with a window,
    # nothing below kv_len - window either. kv_len == 0 skips everything.
    needed = ki * bk < kv_len
    if window is not None:
        needed &= ki * bk + bk > kv_len - window

    @pl.when(needed)
    def _block():
        q = q_ref[0].astype(jnp.float32)                # [KV, gp, hd]
        k = k_ref[0].astype(jnp.float32)                # [KV, bk, hd]
        v = v_ref[0].astype(jnp.float32)
        kv = k.shape[0]

        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        # s [KV, gp, bk]; the mask is head-independent
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (gp, bk), 1)
        mask = kpos < kv_len
        if window is not None:
            mask &= kpos >= kv_len - window
        maskf = jnp.broadcast_to(mask[None], (kv, gp, bk))
        s = jnp.where(maskf, s, NEG_INF)

        m_prev = m_scr[...]                             # [KV, gp]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.where(maskf, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        acc_scr[...] = acc_scr[...] * alpha[..., None] + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)   # kv_len==0 rows -> zeros
        o_ref[0] = (acc_scr[...] / safe[..., None]).astype(o_ref.dtype)


def batched_decode_attention_bhmd(q, k, v, kv_len, *,
                                  window: Optional[int] = None,
                                  bk: int = 256, interpret: Optional[bool] = None):
    """q [B,H,hd]; k/v [B,KV,M,hd]; kv_len [B] -> o [B,H,hd].

    ``bk`` is clamped to the cache width (non-multiple tails are padded
    and masked), so small-cache configs neither fail nor over-read.
    """
    B, H, hd = q.shape
    KV, M = k.shape[1], k.shape[2]
    grp = H // KV
    gp = max(grp, _SUBLANE)
    bk = min(bk, max(M, 8))
    pk = (-M) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nk = (M + pk) // bk
    # GQA-fold query heads onto their KV head, pad the group rows to a
    # sublane tile (padding rows are zero: they cost nothing and their
    # outputs are sliced away — zeros stay finite through the softmax)
    qf = q.reshape(B, KV, grp, hd)
    if gp != grp:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, gp - grp), (0, 0)))

    kernel = functools.partial(_batched_decode_kernel,
                               scale=1.0 / math.sqrt(hd), window=window,
                               bk=bk, gp=gp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, KV, gp, hd), lambda b, j, kv_len: (b, 0, 0, 0)),
            pl.BlockSpec((1, KV, bk, hd), lambda b, j, kv_len: (b, 0, j, 0)),
            pl.BlockSpec((1, KV, bk, hd), lambda b, j, kv_len: (b, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, gp, hd),
                               lambda b, j, kv_len: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, gp), jnp.float32),
            pltpu.VMEM((KV, gp), jnp.float32),
            pltpu.VMEM((KV, gp, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(kv_len, jnp.int32), qf, k, v)
    return out[:, :, :grp].reshape(B, H, hd)
