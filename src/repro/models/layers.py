"""Core neural-net primitives (pure JAX, no flax).

Parameters are nested dicts of jnp arrays. Every init_* returns such a
dict; every apply function is pure. Attention dispatches to the Pallas
kernels in ``repro.kernels`` when ``repro.kernels.dispatch.use_pallas()``
is enabled; the default path is pure jnp (XLA) and is the oracle the
kernels are validated against.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, dtype=jnp.float32,
               scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * jnp.asarray(scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def norm_init(d: int, *, bias: bool = False, dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if bias:
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def rms_norm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dt)


def layer_norm(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(dt)


# --------------------------------------------------------------------------
# rotary position embedding
# --------------------------------------------------------------------------

def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions [..., S] -> cos/sin [..., S, head_dim//2] (float32)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [..., S, H, hd]; cos/sin [..., S, hd//2] (broadcast over heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# attention (jnp reference path; Pallas kernels mirror this math)
# --------------------------------------------------------------------------

def _expand_kv(k, n_rep: int):
    """[B,S,KV,hd] -> [B,S,KV*n_rep,hd] by repeating each kv head."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)
                            ).reshape(b, s, kv * n_rep, hd)


def attention(q, k, v, *, causal: bool, window: Optional[int] = None,
              q_offset=0, kv_len: Optional[jnp.ndarray] = None):
    """Scaled dot-product attention with GQA, causal and sliding-window masks.

    q: [B, Sq, H, hd]; k/v: [B, Sk, KV, hd]. ``q_offset`` is the absolute
    position of q[0] relative to k[0] (decode: Sk-1 typically) — a scalar,
    or a per-row [B] array (ragged chunked prefill: each batch row sits at
    its own offset into its KV lines).
    ``kv_len`` optionally masks out cache positions >= kv_len (ragged decode).
    Returns [B, Sq, H, hd].
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    n_rep = h // kv
    k = _expand_kv(k, n_rep)
    v = _expand_kv(v, n_rep)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_off = jnp.asarray(q_offset)
    if q_off.ndim:                                     # per-row offsets [B]
        qpos = jnp.arange(sq)[None, :, None] + q_off[:, None, None]  # [B,Sq,1]
        kpos = jnp.arange(sk)[None, None, :]                         # [1,1,Sk]
        mask = jnp.ones((b, sq, sk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len[:, None, None]
        mask = mask[:, None]                                      # [B,1,Sq,Sk]
    else:
        qpos = jnp.arange(sq)[:, None] + q_offset          # [Sq,1]
        kpos = jnp.arange(sk)[None, :]                     # [1,Sk]
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask = mask[None] & (kpos[None] < kv_len[:, None, None])  # [B,Sq,Sk]
            mask = mask[:, None]                                      # [B,1,Sq,Sk]
        else:
            mask = mask[None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows that are fully masked produce NaN; zero them (cannot happen for
    # causal self-attention but can for ragged kv_len=0)
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def decode_attention(q, k, v, *, kv_len):
    """One-query attention against K/V lines as stored, heads grouped.

    q [B,1,H,hd]; k/v [B,M,KV,hd]; kv_len [B]. The math of
    :func:`attention` with only the ``kv_len`` mask, but q is viewed as
    [B, KV, H/KV, hd] and contracted against the stored KV heads, so the
    cache is never repeated to H heads. Returns [B,1,H,hd].
    """
    b, _, h, hd = q.shape
    m, kv = k.shape[1], k.shape[2]
    qg = q.reshape(b, kv, h // kv, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bgrd,bmgd->bgrm", qg, k,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(m)[None, :] < kv_len[:, None])[:, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    # a row with nothing in its cache would be NaN; zero it, as attention does
    probs = jnp.where(jnp.any(mask, axis=-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bgrm,bmgd->bgrd", probs.astype(v.dtype), v)
    return out.reshape(b, 1, h, hd)


def ragged_prefill_attention(q, k, v, *, pos0, take=None,
                             window: Optional[int] = None):
    """Reference twin of ``kernels.ops.ragged_prefill_attention``.

    q [G,Sq,H,hd]; k/v [G,W,KV,hd]; pos0/take [G]. Row ``g`` carries
    ``take[g]`` valid query tokens whose absolute positions start at
    ``pos0[g]`` within its W cache lines; causal/window masks are applied
    at those per-row offsets, and padding query rows (>= take) are
    emitted as zeros exactly like the kernel (they never contaminate
    valid lanes: chunked prefill only writes/reads the first ``take``
    positions). ``take=None`` means every row is fully valid.
    """
    g, s = q.shape[:2]
    out = attention(q, k, v, causal=True, window=window, q_offset=pos0)
    if take is None:
        return out
    valid = jnp.arange(s)[None, :] < take[:, None]
    return jnp.where(valid[:, :, None, None], out, jnp.zeros_like(out))


def blocked_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                      q_offset=0, block_q: int = 512):
    """Flash-style attention at the XLA level: lax.map over q blocks with
    per-block fused mask+softmax. Never materializes the [B,H,Sq,Sk]
    score tensor — peak live bytes drop from O(Sq·Sk) to O(block_q·Sk).
    This is the §Perf fix for the memory-bound prefill shapes (the Pallas
    flash kernel is the TPU-native equivalent; this path is what the
    dry-run lowers).
    """
    b, sq, h, hd = q.shape
    pb = (-sq) % block_q
    if pb:
        q = jnp.pad(q, ((0, 0), (0, pb), (0, 0), (0, 0)))
    nblk = (sq + pb) // block_q

    def one_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, axis=1)
        return attention(qi, k, v, causal=causal, window=window,
                         q_offset=q_offset + i * block_q)

    out = jax.lax.map(one_block, jnp.arange(nblk))        # [nblk,B,bq,H,hd]
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq + pb, h, hd)
    return out[:, :sq]


def init_attn(key, cfg, *, dtype=jnp.float32, cross: bool = False):
    ks = jax.random.split(key, 6)
    hd = cfg.resolved_head_dim
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype=dtype,
                         scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = norm_init(hd, dtype=dtype)
        p["k_norm"] = norm_init(hd, dtype=dtype)
    return p


def attn_qkv(p, cfg, x, *, positions=None, kv_x=None, rope: bool = True):
    """Project to q/k/v heads; apply qk-norm and rope. kv_x for cross-attn."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    kv_src = x if kv_x is None else kv_x
    skv = kv_src.shape[1]
    q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
    k = dense(p["wk"], kv_src).reshape(b, skv, cfg.n_kv_heads, hd)
    v = dense(p["wv"], kv_src).reshape(b, skv, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if rope:
        qpos = positions if positions is not None else jnp.arange(s)
        cos, sin = rope_cos_sin(qpos, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        if kv_x is None:
            k = apply_rope(k, cos, sin)
        else:
            kcos, ksin = rope_cos_sin(jnp.arange(skv), hd, cfg.rope_theta)
            k = apply_rope(k, kcos, ksin)
    return q, k, v


def _seq_shard(x, cfg):
    if not cfg.shard_attn_seq:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            x, P(None, "model", *([None] * (x.ndim - 2))))
    except (ValueError, RuntimeError):   # no mesh context (CPU tests)
        return x


def full_attention(q, k, v, cfg, *, causal, window):
    """Training/prefill attention honoring the §Perf knobs."""
    bq = cfg.attention_block_q
    if cfg.shard_attn_seq:
        q = _seq_shard(q, cfg)
    if bq is not None and q.shape[1] > bq:
        out = blocked_attention(q, k, v, causal=causal, window=window,
                                block_q=bq)
    else:
        out = _dispatch_attention(q, k, v, causal=causal, window=window)
    if cfg.shard_attn_seq:
        out = _seq_shard(out, cfg)
    return out


def self_attention_block(p, cfg, x, *, causal=True, window=None):
    """Full-sequence self-attention (training / prefill)."""
    q, k, v = attn_qkv(p, cfg, x)
    out = full_attention(q, k, v, cfg, causal=causal, window=window)
    b, s, _, _ = q.shape
    return dense(p["wo"], out.reshape(b, s, cfg.q_dim))


def cross_attention_block(p, cfg, x, memory):
    q, k, v = attn_qkv(p, cfg, x, kv_x=memory, rope=False)
    out = _dispatch_attention(q, k, v, causal=False, window=None)
    b, s, _, _ = q.shape
    return dense(p["wo"], out.reshape(b, s, cfg.q_dim))


def _dispatch_attention(q, k, v, *, causal, window, q_offset=0, kv_len=None,
                        take=None):
    from repro.kernels import dispatch as kd
    q_off = jnp.asarray(q_offset)
    if q_off.ndim and causal and kv_len is None:
        # per-row offsets [B]: ragged chunked prefill
        if kd.use_pallas():
            from repro.kernels import ops as kops
            tk = (take if take is not None
                  else jnp.full((q.shape[0],), q.shape[1], jnp.int32))
            return kops.ragged_prefill_attention(q, k, v, q_off, tk,
                                                 window=window)
        return ragged_prefill_attention(q, k, v, pos0=q_off, take=take,
                                        window=window)
    if kd.use_pallas() and kv_len is None and q.shape[1] > 1:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset)
    if kd.use_pallas() and q.shape[1] == 1 and kv_len is not None:
        from repro.kernels import ops as kops
        return kops.decode_attention(q, k, v, kv_len=kv_len, window=window)
    if q.shape[1] == 1 and kv_len is not None and not causal \
            and window is None:
        return decode_attention(q, k, v, kv_len=kv_len)
    return attention(q, k, v, causal=causal, window=window,
                     q_offset=q_offset, kv_len=kv_len)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def init_mlp(key, cfg, *, dtype=jnp.float32, d_ff: Optional[int] = None):
    d_ff = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        return {
            "w_gate": dense_init(ks[0], cfg.d_model, d_ff, dtype=dtype),
            "w_up": dense_init(ks[1], cfg.d_model, d_ff, dtype=dtype),
            "w_down": dense_init(ks[2], d_ff, cfg.d_model, dtype=dtype),
        }
    return {
        "w_up": dense_init(ks[0], cfg.d_model, d_ff, bias=True, dtype=dtype),
        "w_down": dense_init(ks[1], d_ff, cfg.d_model, bias=True, dtype=dtype),
    }


def mlp(p, cfg, x):
    if "w_gate" in p:
        return dense(p["w_down"], jax.nn.silu(dense(p["w_gate"], x)) * dense(p["w_up"], x))
    return dense(p["w_down"], jax.nn.gelu(dense(p["w_up"], x)))


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, *, dtype=jnp.float32):
    return {"w": jax.random.normal(key, (vocab, d_model), dtype) * 0.02}


def embed(p, tokens):
    return p["w"][tokens]


def unembed(p, x):
    return x @ p["w"].T


def softmax_cross_entropy(logits, labels, *, ignore_id: int = -1):
    """Mean CE over non-ignored positions. logits [..., V], labels [...].

    The gold logit is selected with a fused one-hot reduction rather than
    take_along_axis: a gather over a vocab-sharded logits tensor forces
    GSPMD to all-gather the full logits (hundreds of GB at train_4k);
    the iota-compare-multiply-reduce form stays sharded and fuses.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = (labels[..., None] == jnp.arange(V, dtype=labels.dtype))
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = logz - gold
    ok = labels != ignore_id
    return jnp.sum(nll * ok) / jnp.maximum(jnp.sum(ok), 1)
