"""Ragged chunked-prefill flash-attention Pallas TPU kernel.

The serving engine's batched chunked prefill (``serve_prefill_chunk``)
runs G co-resident prompt chunks through one padded call: row ``g`` holds
``take[g]`` valid query tokens whose absolute positions start at
``pos0[g]``, attending that row's slot-pooled KV lines bounded to a
static ``kv_width`` bucket. This kernel is the TPU-native version of that
attention.

Launch geometry (tuned — see the block-size rationale below):

* grid ``(G, Sq/BQ, W/BK)`` with the KV dimension innermost and
  sequential ("arbitrary"), online-softmax (m, l, acc) scratch in VMEM;
* every block carries ALL H query heads and ALL KV heads — one grid
  step computes the whole head stack. GQA query heads are folded into
  their KV group ([H, bq, hd] -> [KV, grp*bq, hd]) so the score and
  weighted-value contractions are each a single KV-batched
  ``dot_general`` instead of H per-head matmuls. Compared to the
  original (G, H, Sq/BQ, W/BK) per-head grid this cuts the step count
  by H (fewer, larger MXU issues; far less per-step grid overhead —
  the term that dominated in interpret mode and lost the CPU microbench
  3x), and it reads each KV block once per q-block instead of once per
  q-head.
* **KV double-buffering**: the innermost KV walk is what Mosaic's
  automatic pipeline double-buffers — while the current KV block is in
  the MXU, the next block's DMA is in flight. The index maps are
  arranged so the q block is revisited (constant across the KV walk,
  fetched once) and only k/v stream, which is exactly the layout the
  pipeliner wants: q-block compute overlaps the next KV-block load.
* **Block-size rationale**: ``bq=128`` matches the MXU tile and keeps
  one q block per serving chunk (engine chunks are <= 128 tokens);
  ``bk=256`` halves the number of sequential KV steps vs 128 (fewer
  pipeline stalls and fewer grid steps) while a [KV, 256, hd] block
  still fits VMEM comfortably for every serving config in the zoo
  (worst case KV=8, hd=128: 1 MiB/buffer). Shrink ``bk`` before ``bq``
  if a future config overflows VMEM: the q block is reused W/bk times,
  the KV block only grp times.

Per-row raggedness rides in scalar-prefetch SMEM (``pos0``, ``take``
int32 [G]); masking is computed against ``pos0[g] + row``:

* query rows >= ``take[g]`` are padding — fully masked, emitted as zeros
  (``take[g] == 0`` rows — pure padding — are all zeros);
* causal: key position <= query position, which also fences off stale
  pool lines past ``pos0[g] + take[g]`` (later chunks see every line an
  earlier chunk wrote, and nothing a previous slot tenant left behind);
* optional sliding ``window``: key position > query position - window.

KV blocks that no valid query row of the current q-block can attend
(beyond the causal extent, or entirely below the window) are skipped via
``pl.when`` — a row early in its prompt pays O(pos0 + take), not
O(kv_width); q-blocks past ``take`` skip the whole KV walk. The skip is
verified to actually fire on engine-shaped traces by
``tests/test_ragged_prefill_kernel.py::test_masked_block_skip_fires``
(NaN-poisoned dead blocks must never contaminate the output — a masked
block that is *computed* rather than skipped turns into NaN through the
0 * NaN weighted-value product).

Validated against layers.ragged_prefill_attention (the jnp reference
twin) in interpret mode on CPU (tests/test_ragged_prefill_kernel.py);
on a TPU the same entry point compiles.
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


# Ref order contract (checked statically by reprolint pallas-contract):
# 2 scalar-prefetch refs (pos0, take), then in_specs, out, scratch —
# the signature arity must match the PrefetchScalarGridSpec below, and
# every BlockSpec index map stays pure arithmetic over
# (grid indices..., prefetch refs...).
def _ragged_prefill_kernel(pos0_ref, take_ref, q_ref, k_ref, v_ref, o_ref,
                           m_scr, l_scr, acc_scr, *, scale: float,
                           window: Optional[int], bq: int, bk: int,
                           grp: int):
    g = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos0 = pos0_ref[g]
    take = take_ref[g]

    # Block-level early exit: the largest valid query row in this q-block
    # is min(take, (qi+1)*bq) - 1, so its causal extent ends at
    # pos0 + that row; a KV block starting past it is fully masked. With a
    # sliding window, blocks entirely below qpos_min - window are dead too.
    # Fully-padded q-blocks (qi*bq >= take) skip the whole KV walk.
    row_hi = jnp.minimum(take, (qi + 1) * bq) - 1          # -1 when take==0
    needed = (qi * bq < take) & (ki * bk <= pos0 + row_hi)
    if window is not None:
        needed &= ki * bk + bk > pos0 + qi * bq - window

    @pl.when(needed)
    def _block():
        q = q_ref[0].astype(jnp.float32)                   # [H, bq, hd]
        k = k_ref[0].astype(jnp.float32)                   # [KV, bk, hd]
        v = v_ref[0].astype(jnp.float32)
        kv = k.shape[0]
        hd = q.shape[2]
        # fold each GQA group's q heads onto their shared KV head: head
        # h = kvh*grp + j lands at rows [j*bq, (j+1)*bq) of batch row kvh
        qg = q.reshape(kv, grp * bq, hd)

        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        # s [KV, grp*bq, bk]
        row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        qpos = pos0 + row
        mask = (row < take) & (kpos <= qpos)
        if window is not None:
            mask &= kpos > qpos - window
        # the mask is head-independent: broadcast over [KV, grp]
        maskf = jnp.broadcast_to(mask[None, None], (kv, grp, bq, bk)
                                 ).reshape(kv, grp * bq, bk)
        s = jnp.where(maskf, s, NEG_INF)

        m_prev = m_scr[...]                                # [KV, grp*bq]
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
        safe = jnp.where(l > 0, l, 1.0)   # fully-masked rows -> zeros
        o_ref[0] = (acc_scr[...] / safe[..., None]
                    ).reshape(o_ref.shape[1:]).astype(o_ref.dtype)


def ragged_prefill_attention_bhsd(q, k, v, pos0, take, *,
                                  window: Optional[int] = None,
                                  bq: int = 128, bk: int = 256,
                                  interpret: Optional[bool] = None):
    """q [G,H,Sq,hd]; k/v [G,KV,W,hd]; pos0/take [G] -> o [G,H,Sq,hd]."""
    G, H, Sq, hd = q.shape
    KV, W = k.shape[1], k.shape[2]
    grp = H // KV
    bq = min(bq, max(Sq, 8))
    bk = min(bk, max(W, 8))
    pq = (-Sq) % bq
    pk = (-W) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (Sq + pq) // bq
    nk = (W + pk) // bk

    kernel = functools.partial(
        _ragged_prefill_kernel, scale=1.0 / math.sqrt(hd), window=window,
        bq=bq, bk=bk, grp=grp)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, nq, nk),
        in_specs=[
            # q revisited across the KV walk (index constant in j): fetched
            # once per (g, qi), resident while k/v stream underneath it
            pl.BlockSpec((1, H, bq, hd),
                         lambda g, i, j, pos0, take: (g, 0, i, 0)),
            pl.BlockSpec((1, KV, bk, hd),
                         lambda g, i, j, pos0, take: (g, 0, j, 0)),
            pl.BlockSpec((1, KV, bk, hd),
                         lambda g, i, j, pos0, take: (g, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, bq, hd),
                               lambda g, i, j, pos0, take: (g, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, grp * bq), jnp.float32),
            pltpu.VMEM((KV, grp * bq), jnp.float32),
            pltpu.VMEM((KV, grp * bq, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, H, Sq + pq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(jnp.asarray(pos0, jnp.int32), jnp.asarray(take, jnp.int32), q, k, v)
    return out[:, :, :Sq]
