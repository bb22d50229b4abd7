"""KV / recurrent-state caches for serving.

Attention caches are either *full* ([B, max_len, KV, hd] per layer, write
at absolute position) or *rolling* (size = window W, write at pos % W) —
the rolling buffer is what makes long_500k decode O(window) for SWA archs
(Mistral-style). Keys are stored post-RoPE, so buffer order is irrelevant
(softmax is permutation-invariant over keys); validity is tracked by a
per-request ``pos`` counter: valid slots = min(pos, W).
"""
from __future__ import annotations

import zlib
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

# Token-block granularity for cross-request KV prefix reuse: prefixes are
# hashed (and shared) in units of this many tokens, so a borrower can only
# skip prefill for whole blocks it matches exactly.
PREFIX_BLOCK = 16


def cache_len(cfg, shape_seq: int, *, margin: int = 8) -> int:
    w = cfg.decode_window()
    if w is not None:
        return w
    return shape_seq + margin


def stored_kv_heads(cfg) -> int:
    """KV heads as stored in the cache. §Perf: expanding GQA heads to the
    model-axis size aligns each chip's cache shard with its q-head group,
    eliminating per-layer cache re-gather at decode (2x memory for the
    8->16 mistral-large case, minus tens of GB of collectives)."""
    return cfg.kv_cache_expand_heads or cfg.n_kv_heads


def expand_kv_for_cache(cfg, k):
    """[B,S,KV,hd] -> [B,S,stored,hd] by repeating each kv head."""
    tgt = stored_kv_heads(cfg)
    kv = k.shape[2]
    if tgt == kv:
        return k
    rep = tgt // kv
    b, s, _, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, rep, hd)
                            ).reshape(b, s, tgt, hd)


def init_attn_cache(cfg, batch: int, max_len: int, n_layers: int,
                    dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    kvh = stored_kv_heads(cfg)
    return {
        "k": jnp.zeros((n_layers, batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((n_layers, batch, max_len, kvh, hd), dtype),
    }


def init_latent_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Latent-attention (MLA) cache: per layer and token one ``c_kv`` line
    [kv_lora_rank] and one roped ``k_pe`` line [qk_rope_head_dim], shared
    by every head, in place of per-head K and V."""
    L = cfg.n_layers
    return {
        "ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((L, batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def write_prefill(cache_k, cache_v, k, v, window: Optional[int]):
    """Single-layer prefill write. cache [B,M,KV,hd]; k/v [B,S,KV,hd].

    Rolling buffers store position p at slot p % M; when the prompt is
    longer than the buffer we keep the last M tokens and roll them into
    their canonical slots so later decode writes evict the oldest entry.
    """
    M = cache_k.shape[1]
    S = k.shape[1]
    if window is not None and S > M:
        k, v = k[:, -M:], v[:, -M:]
        shift = (S - M) % M
        if shift:
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
        S = M
    cache_k = cache_k.at[:, :S].set(k)
    cache_v = cache_v.at[:, :S].set(v)
    return cache_k, cache_v


def write_chunk(cache_k, cache_v, k, v, slot_idx, pos0, take, layer=None):
    """Batched ragged chunk write into a slot-pooled cache.

    cache [B, M, KV, hd] (the engine's shared pool); k/v [G, S, KV, hd]
    right-padded prompt chunks. One batched scatter per cache tensor: row
    ``g`` writes its first ``take[g]`` lines at absolute positions
    [pos0[g], pos0[g]+take[g]) of pool row ``slot_idx[g]`` — no
    per-request cache allocation and no full-pool copy on the host; XLA
    updates a donated pool in place. Padded positions are routed out of
    bounds and dropped, so they can never corrupt lines a row already
    owns and the compiled program is one scatter regardless of G.
    A layer-stacked cache [L, B, M, ...] takes ``layer`` and is written at
    (layer, row, line) in place, with no copy of the layer. Trailing dims
    are free, so latent lines [G, S, C] go the same way.
    """
    lead = () if layer is None else (layer,)
    M = cache_k.shape[len(lead) + 1]
    G, S = k.shape[:2]
    assert S <= M, f"chunk width {S} exceeds cache lines {M}"
    cols = pos0[:, None] + jnp.arange(S)[None, :]            # [G, S]
    cols = jnp.where(jnp.arange(S)[None, :] < take[:, None], cols, M)
    rows = slot_idx[:, None]                                 # [G, 1]
    cache_k = cache_k.at[lead + (rows, cols)].set(k, mode="drop")
    cache_v = cache_v.at[lead + (rows, cols)].set(v, mode="drop")
    return cache_k, cache_v


def prefix_block_hashes(ids, block: int = PREFIX_BLOCK) -> List[int]:
    """Chained crc32 per full token block: ``hashes[b]`` covers tokens
    [0, (b+1)*block), so two prompts sharing hash ``b`` share (modulo
    collisions, which the index resolves by exact token comparison) their
    whole first ``(b+1)*block`` tokens — a single int per boundary gives
    longest-prefix lookup without storing every sub-prefix."""
    n = (len(ids) // block) * block
    if n == 0:
        return []
    arr = np.asarray(ids[:n], np.int32)
    out, h = [], 0
    for i in range(0, n, block):
        h = zlib.crc32(arr[i:i + block].tobytes(), h)
        out.append(h)
    return out


def copy_prefix(pool, src_idx, dst_idx, length, width: int):
    """Batched cross-slot prefix copy on one stacked [L, B, M, ...] pool.

    Row ``g`` copies cache lines [0, length[g]) of pool slot ``src_idx[g]``
    into slot ``dst_idx[g]`` across every layer at once — one gather plus
    one drop-mode scatter (the batched dynamic-update idiom of
    :func:`write_chunk`), regardless of how many borrowers seed this step.
    ``width`` is the static gather width (>= max(length)); lines beyond
    ``length[g]`` are routed out of bounds and dropped.
    """
    M = pool.shape[2]
    G = src_idx.shape[0]
    assert width <= M, f"copy width {width} exceeds cache lines {M}"
    src = pool[:, src_idx, :width]                           # [L,G,W,...]
    cols = jnp.broadcast_to(jnp.arange(width)[None, :], (G, width))
    cols = jnp.where(cols < length[:, None], cols, M)        # [G, W]
    rows = dst_idx[:, None]                                  # [G, 1]
    return pool.at[:, rows, cols].set(src, mode="drop")


def write_decode(cache_k, cache_v, k, v, pos, window: Optional[int]):
    """Write one token per row at per-request absolute position ``pos`` [B].

    cache [B, M, KV, hd]; k/v [B, 1, KV, hd] (trailing dims are free, so
    latent lines [B, 1, C] go the same way). A layer-stacked cache
    [L, B, M, KV, hd] takes ``pos`` as ``(layer, pos)`` and is written at
    ``(layer, row, slot)`` alone: the decode layer scan carries the stack,
    so XLA scatters the B lines in place instead of slicing the layer out
    and writing it back.
    """
    layer = ()
    if isinstance(pos, tuple):
        layer, pos = (pos[0],), pos[1]
    M = cache_k.shape[len(layer) + 1]
    slot = pos % M if window is not None else jnp.minimum(pos, M - 1)
    idx = layer + (jnp.arange(k.shape[0]), slot)
    cache_k = cache_k.at[idx].set(k[:, 0], unique_indices=True)
    cache_v = cache_v.at[idx].set(v[:, 0], unique_indices=True)
    return cache_k, cache_v


def valid_len(pos, max_len: int, window: Optional[int]):
    """Number of valid cache slots after writing token at ``pos`` [B]."""
    n = pos + 1
    return jnp.minimum(n, max_len)
