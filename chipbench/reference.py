"""Plain float32 dense GQA decoder: the reference that decides ``correct``.

Written from the published description of Qwen2 and InternLM2 (pre-norm
RMSNorm blocks, rotary position embedding on the two halves of each head,
grouped-query causal attention over the whole context, SwiGLU MLP, a final
RMSNorm and an output head), in ``jax.numpy`` at float32 with every matrix
product at ``Precision.HIGHEST``. No cache, no batching, no kernels: one
sequence at a time, attention in blocks of queries so that an 8k context
fits beside the weights. It imports nothing of the program and takes its
weights from ``weights.canonical`` with the run's seed.

``mode`` selects the precision: ``"f32"`` is the reference; ``"int8"`` and
``"fp8"`` are the controls, the same computation with every projection and
the output head rounded per output channel to int8 or float8_e4m3, the
step below the bfloat16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.weights import Sizes

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
PROJ = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _round_channels(w, mode: str, axis: int):
    """Round ``w`` to int8 or float8_e4m3 with one scale per output
    channel (``axis`` is the reduced, input axis) and return float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {mode!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, heads, hd] at positions pos [T]; rotate the two halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v):
    """Causal GQA attention. q [T, H, hd]; k, v [T, K, hd]."""
    T, H, hd = q.shape
    K = k.shape[1]
    g = H // K
    nb = T // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, K, g, hd)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HI)
        s = s / jnp.sqrt(jnp.float32(hd))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(T, H * hd)


@partial(jax.jit, static_argnums=(0, 4))
def logits_at(sz: Sizes, w: dict, ids, want, mode: str = "f32"):
    """Logits [P, vocab] at positions ``want`` [P] of the sequence ``ids``
    [T] (T a multiple of ``Q_BLOCK``; padding after the last real token
    does not reach earlier positions)."""
    f32 = jnp.float32
    T = ids.shape[0]
    pos = jnp.arange(T)
    ctl = mode != "f32"

    def proj(x, name, wl):
        m = wl[name]
        m = _round_channels(m, mode, -2) if ctl else m.astype(f32)
        y = jnp.dot(x, m, precision=HI)
        b = "b" + name[1]
        if name in ("wq", "wk", "wv") and b in wl:
            y = y + wl[b].astype(f32)
        return y

    def layer(h, wl):
        x = _rms(h, wl["ln1"].astype(f32), sz.norm_eps)
        q = proj(x, "wq", wl).reshape(T, sz.heads, sz.head_dim)
        k = proj(x, "wk", wl).reshape(T, sz.kv_heads, sz.head_dim)
        v = proj(x, "wv", wl).reshape(T, sz.kv_heads, sz.head_dim)
        q, k = _rope(q, pos, sz.rope_theta), _rope(k, pos, sz.rope_theta)
        h = h + proj(_attention(q, k, v), "wo", wl)
        x = _rms(h, wl["ln2"].astype(f32), sz.norm_eps)
        a = jax.nn.silu(proj(x, "w_gate", wl)) * proj(x, "w_up", wl)
        return h + proj(a, "w_down", wl), None

    per_layer = {k_: w[k_] for k_ in PROJ + ("ln1", "ln2", "bq", "bk", "bv")
                 if k_ in w}
    h = w["embed"][ids].astype(f32)
    h, _ = jax.lax.scan(layer, h, per_layer)
    h = _rms(h[want], w["final_norm"].astype(f32), sz.norm_eps)
    head = w["embed"] if sz.tied else w["lm_head"]
    head = _round_channels(head, mode, -1) if ctl else head.astype(f32)
    return jnp.dot(h, head.T, precision=HI)


def pad_to(n: int, block: int = Q_BLOCK) -> int:
    return max(block, -(-n // block) * block)
