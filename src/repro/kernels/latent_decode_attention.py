"""Absorbed latent (MLA) decode attention, Pallas TPU kernel.

One query per row against that row's stored latent lines, with W_uk
absorbed into the query (``models/mla.py`` ``attend_absorbed`` is the
reference twin)::

    s   = (q_lat . c_kv + q_pe . k_pe) * scale        q_lat [H, C]
    out = softmax(s) c_kv                              [H, C]

read in one online-softmax pass straight out of the layer-stacked pools
``ckv`` [L, B, M, C] and ``kpe`` [L, B, R, M] (lines-minor,
``kvcache.init_latent_cache``) that the decode layer scan carries, live
blocks only:

* the grid is one step per *live* block: ``sum_b cdiv(kv_len[b], bk)``
  steps, a size computed on the device. Step ``i`` reads block
  ``blk[i]`` of row ``row[i]`` (tables built here from ``kv_len`` and
  passed as scalar prefetch with ``layer``), so a dead block costs
  neither a copy nor a grid step, and a row with ``kv_len == 0`` none;
* the pools are indexed by ``(layer, row, block)`` in the BlockSpec index
  maps: no layer slice of the pool is materialised as an operand, and
  Mosaic's pipeline double-buffers the next block's copy (across row
  boundaries too) under the current block's compute;
* only a row's last block can hold lines at or past ``kv_len``: its
  scores are masked and its dead ``c_kv`` lines zeroed before
  ``P . c_kv``, so stale lines never enter the result, even NaN;
* the row's new ``k_pe`` line (at ``kv_len - 1``) is merged into that
  last block, which is the row's one ``kpe`` output block (aliased to
  the pool) and goes back to HBM once, when the row is done. Nothing
  else in the decode program touches ``kpe``, so the compiler keeps it
  in the layout the kernel reads: an XLA scatter of the line made it
  re-lay the whole pool out around the kernel at every layer in
  programs of few slots;
* ``q_lat``, ``c_kv`` and ``k_pe`` enter the MXU in the pool dtype with
  float32 accumulation; the running max, sum and ``acc`` [H, C] are
  float32 scratch; ``P`` is cast to the pool dtype for ``P . c_kv``.

Rows that read nothing leave their output block unwritten; the wrapper
returns zeros there. Validated against ``attend_absorbed`` in interpret
mode on CPU (``tests/test_latent_decode_kernel.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import resolve_interpret

NEG_INF = -1e30
BLOCK = 1024    # latent lines per block; chosen by a chip sweep (PERF.md)


def block_lines(max_len: int, bk: int = BLOCK) -> int:
    """Lines per block for a pool of ``max_len`` lines a row: ``bk``,
    clamped to the pool width."""
    return min(bk, max_len)


# Ref order contract (checked statically by reprolint pallas-contract):
# 4 scalar-prefetch refs (layer, kv_len, row, blk), then in_specs, outs,
# scratch.
def _latent_decode_kernel(layer_ref, kv_len_ref, row_ref, blk_ref, q_ref,
                          qpe_ref, new_ref, c_ref, r_ref, o_ref, kpe_ref,
                          m_scr, l_scr, acc_scr, *, scale: float, bk: int):
    i = pl.program_id(0)
    j = blk_ref[i]
    kv_len = kv_len_ref[row_ref[i]]
    last = j == (kv_len - 1) // bk

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(masked: bool):
        c = c_ref[...]                                    # [bk, C]
        r = r_ref[...]                                    # [R, bk]
        pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if masked:
            # the row's new k_pe line lands at kv_len - 1 of this block;
            # the block goes back to the pool once the row is done
            r = jnp.where(pos == kv_len - 1, new_ref[...], r)
            kpe_ref[...] = r
        # q_lat . c_kv^T and q_pe . k_pe, k_pe already [R, bk]
        nt = (((1,), (1,)), ((), ()))
        s = (jax.lax.dot_general(q_ref[...], c, nt,
                                 preferred_element_type=jnp.float32)
             + jnp.dot(qpe_ref[...], r, preferred_element_type=jnp.float32))
        s = s * scale                                     # [H, bk]
        if masked:
            s = jnp.where(pos < kv_len, s, NEG_INF)
            line = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
            c = jnp.where(line < kv_len, c, jnp.zeros_like(c))
        m_prev = m_scr[...]                               # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(last))
    def _full():
        attend(masked=False)

    @pl.when(last)
    def _last():
        attend(masked=True)
        o_ref[...] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def latent_decode_attention(q_lat, q_pe, k_new, ckv, kpe, layer, kv_len, *,
                            scale: float, bk: int = BLOCK,
                            interpret: Optional[bool] = None):
    """q_lat [B,H,C], q_pe [B,H,R], k_new [B,R]; ckv [L,B,M,C],
    kpe [L,B,R,M]; layer (scalar); kv_len [B] -> (out [B,H,C] in the pool
    dtype, kpe). Row b's new k_pe line ``k_new[b]`` is written at line
    ``kv_len[b] - 1`` of ``layer`` (rows with ``kv_len == 0`` write none),
    in place: ``kpe`` is aliased to the second output. ``out[b]`` is the
    softmax-weighted sum of row b's first ``kv_len[b]`` c_kv lines, zeros
    where ``kv_len[b] == 0``. The queries are cast to the pool dtype."""
    B, H, C = q_lat.shape
    R = q_pe.shape[-1]
    M = ckv.shape[2]
    bk = block_lines(M, bk)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    # step i -> (row, block) over the live blocks, rows in order
    nblk = (kv_len + bk - 1) // bk
    ends = jnp.cumsum(nblk)
    steps = jnp.arange(B * pl.cdiv(M, bk), dtype=jnp.int32)
    # rows whose blocks all come before step i (a compare and a sum: a
    # search would be a loop of its own in every layer)
    row = jnp.minimum(jnp.sum(ends[None, :] <= steps[:, None], axis=1,
                              dtype=jnp.int32), B - 1)
    blk = steps - (ends - nblk)[row]
    kernel = functools.partial(_latent_decode_kernel, scale=scale, bk=bk)

    def by_row(i, layer, kv_len, row, blk):
        return row[i], 0, 0

    def by_block(i, layer, kv_len, row, blk):
        return layer[0], row[i], blk[i], 0

    def by_block_t(i, layer, kv_len, row, blk):
        return layer[0], row[i], 0, blk[i]

    def by_last_block_t(i, layer, kv_len, row, blk, bk=bk):
        return layer[0], row[i], 0, (kv_len[row[i]] - 1) // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(ends[-1],),
        in_specs=[
            pl.BlockSpec((None, H, C), by_row),
            pl.BlockSpec((None, H, R), by_row),
            pl.BlockSpec((None, R, 1), by_row),
            pl.BlockSpec((None, None, bk, C), by_block),
            pl.BlockSpec((None, None, R, bk), by_block_t),
        ],
        # a row's k_pe output block is its last block, written back once
        out_specs=[pl.BlockSpec((None, H, C), by_row),
                   pl.BlockSpec((None, None, R, bk), by_last_block_t)],
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, C), jnp.float32),
        ],
    )
    out, kpe = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((B, H, C), ckv.dtype),
                   jax.ShapeDtypeStruct(kpe.shape, kpe.dtype)),
        input_output_aliases={8: 1},
        name="latent_decode_attention",
        # a row's blocks run in order, carrying the softmax in scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), kv_len, row, blk,
      q_lat.astype(ckv.dtype), q_pe.astype(kpe.dtype),
      k_new.astype(kpe.dtype)[:, :, None], ckv, kpe)
    return jnp.where(kv_len[:, None, None] > 0, out, jnp.zeros_like(out)), kpe
