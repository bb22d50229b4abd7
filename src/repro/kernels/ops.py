"""Jit'd public wrappers around the Pallas kernels.

Model code calls these through layers._dispatch_attention with the
[B,S,H,hd] layout; the wrappers transpose to the kernels' [B,H,S,hd]
blocked layout and handle GQA head mapping and padding. Each kernel
resolves interpret mode from the backend (CPU interprets; a TPU
compiles).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.batched_decode_attention import batched_decode_attention_bhmd
# the latent decode kernel takes the model's layout as it is: no wrapper
from repro.kernels.latent_decode_attention import (  # noqa: F401
    block_lines as latent_block_lines, latent_decode_attention)
from repro.kernels.ragged_prefill_attention import ragged_prefill_attention_bhsd
from repro.kernels.rmsnorm import rmsnorm_2d


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, q_offset: int = 0,
                    bq: int = 128, bk: int = 128):
    """q [B,Sq,H,hd]; k/v [B,Sk,KV,hd] -> [B,Sq,H,hd]."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                             q_offset=q_offset, bq=bq, bk=bk)
    return jnp.swapaxes(o, 1, 2)


@partial(jax.jit, static_argnames=("window", "bq", "bk"))
def ragged_prefill_attention(q, k, v, pos0, take, *,
                             window: Optional[int] = None,
                             bq: int = 128, bk: int = 256):
    """q [G,S,H,hd]; k/v [G,W,KV,hd]; pos0/take [G] -> [G,S,H,hd].

    Batched ragged chunked-prefill attention: row ``g`` holds ``take[g]``
    valid query tokens at absolute offset ``pos0[g]`` into its W pooled
    KV lines (W is the engine's static ``kv_width`` bucket). Padding
    query rows (>= take) come back as zeros. Defaults are the tuned
    serving blocks (bq = one engine chunk, bk = half a max-width cache
    walk — see the kernel module docstring for the rationale).
    """
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = ragged_prefill_attention_bhsd(qt, kt, vt, pos0, take, window=window,
                                      bq=bq, bk=bk)
    return jnp.swapaxes(o, 1, 2)


@partial(jax.jit, static_argnames=("window", "bk"))
def decode_attention(q, k, v, *, kv_len, window: Optional[int] = None,
                     bk: int = 256):
    """q [B,1,H,hd]; k/v [B,M,KV,hd]; kv_len [B] -> [B,1,H,hd].

    One launch covers every slot: the batched decode kernel grids
    (B, M/bk) with the whole GQA head stack folded into each block and
    per-slot ``kv_len`` in SMEM. Rolling-window caches already bound M
    to the window and the engine passes ``window=None``; an explicit
    ``window`` applies the sliding mask over a full cache
    (``kv_len - window <= kpos < kv_len``).
    """
    qt = q[:, 0]                                     # [B,H,hd]
    kt = jnp.swapaxes(k, 1, 2)                       # [B,KV,M,hd]
    vt = jnp.swapaxes(v, 1, 2)
    o = batched_decode_attention_bhmd(qt, kt, vt, kv_len, window=window,
                                      bk=bk)
    return o[:, None]


@partial(jax.jit, static_argnames=("chunk",))
def chunked_gla(q, k, v, log_a, *, chunk: int = 128):
    """q,k [B,T,H,Dk]; v [B,T,H,Dv]; log_a [B,T,H] -> y [B,T,H,Dv].

    Pallas kernel for the Mamba2/mLSTM recurrence (models use the XLA path
    in models/linear_recurrence.py; this is the TPU-native equivalent).
    """
    from repro.kernels.chunked_gla import chunked_gla_bhtd
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    lat = jnp.moveaxis(log_a, 1, 2)
    y = chunked_gla_bhtd(qt, kt, vt, lat, chunk=chunk)
    return jnp.moveaxis(y, 1, 2)


@partial(jax.jit, static_argnames=("eps", "bn"))
def rmsnorm(x, scale, *, eps: float = 1e-5, bn: int = 256):
    """x [..., D] -> [..., D]."""
    shape = x.shape
    y = rmsnorm_2d(x.reshape(-1, shape[-1]), scale, eps=eps, bn=bn)
    return y.reshape(shape)
