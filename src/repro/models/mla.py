"""Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1).

For hidden states ``h`` at positions ``p`` (no query compression)::

    [q_nope | q_pe] = W_q h                  H heads of N + R
    [c_kv | k_pe]   = W_kva h                C + R, one k_pe for all heads
    c_kv            = RMSNorm(c_kv)          (kv_a_layernorm)
    q_pe, k_pe      = rope(q_pe, p), rope(k_pe, p)     YaRN frequencies
    [k_nope | v]    = W_kvb c_kv             H heads of N + V
    s               = (q_nope . k_nope + q_pe . k_pe) * (N + R)^-0.5 * m^2
    out             = W_o concat_h(softmax(s) v)

with ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` under YaRN (1
otherwise). The cache keeps one latent line per token and layer: ``c_kv``
(C values) and the roped ``k_pe`` (R values), shared by every head.

Prefill decompresses the lines it attends (``attend_lines``). Decode
(``attend_absorbed``) never does: ``W_uk`` (the k_nope part of
``W_kvb``) is folded into the query, ``q_lat = q_nope W_uk^T`` [H, C],
so ``q_nope . k_nope = q_lat . c_kv``; and ``W_uv`` into the output,
``P v = (P c_kv) W_uv``. It reads only the stored latent lines. On a
TPU backend the served decode (``decode_attend``) reads them with
one Pallas kernel straight out of the layer-stacked pool, live blocks
only (``kernels/latent_decode_attention.py``); ``attend_absorbed`` is
that kernel's reference twin and serves every other backend.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as kd
from repro.kernels import ops as kops
from repro.models import kvcache as KV
from repro.models import layers as L


def init_mla(key, cfg, *, dtype=jnp.float32):
    H, D, C = cfg.n_heads, cfg.d_model, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], D, H * (N + R), dtype=dtype),
        "wkv_a": L.dense_init(ks[1], D, C + R, dtype=dtype),
        "kv_norm": L.norm_init(C, dtype=dtype),
        "wkv_b": L.dense_init(ks[2], C, H * (N + V), dtype=dtype),
        "wo": L.dense_init(ks[3], H * V, D, dtype=dtype),
    }


def softmax_scale(cfg) -> float:
    s = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.yarn_mscale_all_dim:
        s *= L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return s


def _rope(cfg, positions):
    """cos/sin [..., S, R/2]; YaRN's cos/sin scale is the ratio of its two
    mscales (1 where they are equal, as in DeepSeek-V2)."""
    scale = (L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / L.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
             if cfg.yarn_mscale_all_dim else 1.0)
    return L.rope_cos_sin_freq(
        positions, L.rope_inv_freq(cfg, cfg.qk_rope_head_dim), scale)


def project(p, cfg, h, positions):
    """h [B, S, D] at positions [B, S] -> q_nope [B,S,H,N], q_pe [B,S,H,R]
    (roped), c_kv [B,S,C] (normed) and k_pe [B,S,R] (roped)."""
    b, s, _ = h.shape
    H, C = cfg.n_heads, cfg.kv_lora_rank
    N, R = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = L.dense(p["wq"], h).reshape(b, s, H, N + R)
    kv = L.dense(p["wkv_a"], h)
    c_kv = L.rms_norm(p["kv_norm"], kv[..., :C], cfg.norm_eps)
    cos, sin = _rope(cfg, positions)
    q_pe = L.apply_rope(q[..., N:], cos, sin)
    k_pe = L.apply_rope(kv[..., None, C:], cos, sin)[..., 0, :]
    return q[..., :N], q_pe, c_kv, k_pe


def _w_kvb(p, cfg):
    """W_kvb as [C, H, N + V]."""
    return p["wkv_b"]["w"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                                   cfg.qk_nope_head_dim + cfg.v_head_dim)


def attend_lines(p, cfg, q_nope, q_pe, c_kv, k_pe, pos0, take=None):
    """Causal attention of S queries per row at offsets ``pos0`` [G] over
    W stored lines per row (c_kv [G,W,C], k_pe [G,W,R]), decompressed to
    per-head K and V. Query rows at or past ``take`` come out as zeros.
    Returns [G, S, H*V]."""
    g, w = c_kv.shape[:2]
    H, N = cfg.n_heads, cfg.qk_nope_head_dim
    kv = jnp.einsum("gwc,chd->gwhd", c_kv, _w_kvb(p, cfg).astype(c_kv.dtype))
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :], (g, w, H, k_pe.shape[-1]))
    k = jnp.concatenate([kv[..., :N], k_pe.astype(kv.dtype)], -1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
    out = L.ragged_prefill_attention(q, k, kv[..., N:], pos0=pos0, take=take,
                                     scale=softmax_scale(cfg))
    return out.reshape(g, q.shape[1], -1)


def _absorb_query(p, cfg, q_nope, dtype):
    """q_nope [B,1,H,N] -> (q_lat [B,H,C], W_kvb [C,H,N+V]) in ``dtype``:
    W_uk folded into the query."""
    w = _w_kvb(p, cfg).astype(dtype)
    return (jnp.einsum("bhn,chn->bhc", q_nope[:, 0],
                       w[..., :cfg.qk_nope_head_dim]), w)


def attend_absorbed(p, cfg, q_nope, q_pe, c_kv, k_pe, kv_len):
    """One query per row (q_nope [B,1,H,N], q_pe [B,1,H,R]) against the
    row's stored lines (c_kv [B,M,C], k_pe [B,M,R]), the first ``kv_len``
    [B] of them valid, with W_uk and W_uv absorbed. Returns [B, 1, H*V]."""
    b = q_nope.shape[0]
    N = cfg.qk_nope_head_dim
    q_lat, w = _absorb_query(p, cfg, q_nope, c_kv.dtype)
    s = (jnp.einsum("bhc,bmc->bhm", q_lat, c_kv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bmr->bhm", q_pe[:, 0].astype(k_pe.dtype), k_pe,
                      preferred_element_type=jnp.float32))
    s = s * softmax_scale(cfg)
    mask = (jnp.arange(c_kv.shape[1])[None, :] < kv_len[:, None])[:, None]
    probs = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    probs = jnp.where(jnp.any(mask, -1, keepdims=True), probs, 0.0)
    o_lat = jnp.einsum("bhm,bmc->bhc", probs.astype(c_kv.dtype), c_kv)
    out = jnp.einsum("bhc,chv->bhv", o_lat, w[..., N:])
    return out.reshape(b, 1, -1)


def attend_absorbed_kernel(p, cfg, q_nope, q_pe, k_new, ckv, kpe, layer,
                           kv_len, **kernel_kw):
    """``attend_absorbed`` over ``layer`` of the layer-stacked pools
    (ckv [L,B,M,C], kpe [L,B,R,M]) by the Pallas kernel, which first
    writes each row's new k_pe line (k_new [B,1,R]) at ``kv_len - 1``,
    then reads the row's first ``kv_len`` lines in place: no layer slice
    is made. ``kernel_kw`` (``bk``, ``interpret``) goes to the kernel.
    Returns (out [B,1,H*V], kpe)."""
    b = q_nope.shape[0]
    q_lat, w = _absorb_query(p, cfg, q_nope, ckv.dtype)
    o_lat, kpe = kops.latent_decode_attention(
        q_lat, q_pe[:, 0], k_new[:, 0], ckv, kpe, layer, kv_len,
        scale=softmax_scale(cfg), **kernel_kw)
    out = jnp.einsum("bhc,chv->bhv", o_lat, w[..., cfg.qk_nope_head_dim:])
    return out.reshape(b, 1, -1), kpe


def decode_attend(p, cfg, q_nope, q_pe, c_new, k_new, ckv, kpe, layer, pos,
                  kv_len):
    """One decode step of the latent attention over ``layer`` of the
    stacked pools: each row's new line (c_new [B,1,C], k_new [B,1,R]) is
    written at ``pos`` in place, then the row's first ``kv_len`` lines are
    attended in the absorbed form. On a TPU backend the Pallas kernel
    writes the k_pe line and reads the live blocks straight out of the
    pools; elsewhere the layer's lines are sliced out (cast to the query
    dtype) through ``attend_absorbed``. Returns (out [B,1,H*V], ckv, kpe).
    """
    c_new, k_new = c_new.astype(ckv.dtype), k_new.astype(kpe.dtype)
    if not kd.interpret_mode():
        # the kernel writes the k_pe line; c_kv's is written here
        line = jnp.minimum(pos, ckv.shape[2] - 1)
        ckv = ckv.at[layer, jnp.arange(line.shape[0]), line].set(
            c_new[:, 0], unique_indices=True)
        out, kpe = attend_absorbed_kernel(p, cfg, q_nope, q_pe, k_new, ckv,
                                          kpe, layer, kv_len)
        return out, ckv, kpe
    ckv, kpe = KV.write_decode(ckv, KV.lines_major(kpe), c_new, k_new,
                               (layer, pos), None)
    c_kv, k_pe = (jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
                  .astype(q_nope.dtype) for x in (ckv, kpe))
    out = attend_absorbed(p, cfg, q_nope, q_pe, c_kv, k_pe, kv_len)
    return out, ckv, KV.lines_major(kpe)
