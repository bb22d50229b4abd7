"""Per-family block stacks: init, full forward (train/prefill) and one-token
decode, all with ``lax.scan`` over stacked layer parameters (compile time
O(1) in depth; 88-layer mistral-large lowers as one scanned body).

Caches are pytrees whose leading axis is the layer stack, threaded through
the same scan.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import kvcache as KV



# Cost-accounting hook: XLA's cost_analysis counts a while-loop body once,
# so the dry-run lowers shallow depth variants with fully-unrolled layer
# scans (set via set_scan_unroll) and extrapolates per-layer costs.
_SCAN_UNROLL = False


def set_scan_unroll(v: bool) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = bool(v)


def layer_scan(body, init, xs):
    return jax.lax.scan(body, init, xs, unroll=True if _SCAN_UNROLL else 1)

def stacked_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _maybe_remat(fn, enabled: bool, policy: Optional[str] = None):
    if not enabled:
        return fn
    pol = None
    if policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
    elif policy == "nothing":
        pol = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=pol)


# ==========================================================================
# Dense / MoE / VLM decoder layer
# ==========================================================================

def init_decoder_layer(key, cfg, *, dtype=jnp.float32, dense=False):
    """One layer; ``dense`` makes a leading dense layer of an expert stack
    (a SwiGLU of width ``dense_d_ff``)."""
    ks = jax.random.split(key, 4)
    attn = MLA.init_mla if cfg.is_mla else L.init_attn
    p = {
        "ln1": L.norm_init(cfg.d_model, dtype=dtype),
        "attn": attn(ks[0], cfg, dtype=dtype),
        "ln2": L.norm_init(cfg.d_model, dtype=dtype),
    }
    if cfg.family == "moe" and not dense:
        p["moe"] = M.init_moe(ks[1], cfg, dtype=dtype)
    else:
        p["mlp"] = L.init_mlp(ks[1], cfg, dtype=dtype,
                              d_ff=cfg.dense_d_ff if dense else None)
    return p


def decoder_layer_fwd(p, cfg, h, *, window=None):
    """Full-sequence layer. Returns (h, aux)."""
    a = L.self_attention_block(p["attn"], cfg, L.rms_norm(p["ln1"], h, cfg.norm_eps),
                               causal=True, window=window)
    h = h + a
    hn = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    if "moe" in p:
        y, aux = M.moe_forward(p["moe"], cfg, hn)
    else:
        y, aux = L.mlp(p["mlp"], cfg, hn), jnp.zeros((), jnp.float32)
    return h + y, aux


def decoder_layer_prefill(p, cfg, h, ck, cv, *, window=None):
    """Layer forward that also fills this layer's KV cache."""
    hn = L.rms_norm(p["ln1"], h, cfg.norm_eps)
    q, k, v = L.attn_qkv(p["attn"], cfg, hn)
    out = L.full_attention(q, k, v, cfg, causal=True, window=window)
    b, s = h.shape[:2]
    h = h + L.dense(p["attn"]["wo"], out.reshape(b, s, cfg.q_dim))
    ck, cv = KV.write_prefill(ck, cv,
                              KV.expand_kv_for_cache(cfg, k).astype(ck.dtype),
                              KV.expand_kv_for_cache(cfg, v).astype(cv.dtype),
                              window)
    hn = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    if "moe" in p:
        y, _ = M.moe_forward(p["moe"], cfg, hn)
    else:
        y = L.mlp(p["mlp"], cfg, hn)
    return h + y, ck, cv


def decoder_layer_decode(p, cfg, h, ck, cv, layer, pos, *, window=None):
    """One-token step of layer ``layer``. h [B,1,D]; pos [B] absolute
    position; ck/cv the whole layer-stacked cache [L,B,M,KV,hd].

    The new K/V lines are written into the stack in place and attention
    reads the layer's lines as stored, with no copy of the layer."""
    hn = L.rms_norm(p["ln1"], h, cfg.norm_eps)
    q, k, v = L.attn_qkv(p["attn"], cfg, hn, positions=pos[:, None])
    ck, cv = KV.write_decode(ck, cv,
                             KV.expand_kv_for_cache(cfg, k).astype(ck.dtype),
                             KV.expand_kv_for_cache(cfg, v).astype(cv.dtype),
                             (layer, pos), window)
    kvl = KV.valid_len(pos, ck.shape[2], window)
    ck_l = jax.lax.dynamic_index_in_dim(ck, layer, 0, keepdims=False)
    cv_l = jax.lax.dynamic_index_in_dim(cv, layer, 0, keepdims=False)
    # window=None here on purpose: rolling caches bound M to the window
    # and kv_len tracks validity. Under use_pallas() this is the batched
    # decode kernel — all slots/heads in one launch.
    out = L._dispatch_attention(q, ck_l.astype(q.dtype), cv_l.astype(q.dtype),
                                causal=False, window=None, kv_len=kvl)
    b = h.shape[0]
    h = h + L.dense(p["attn"]["wo"], out.reshape(b, 1, cfg.q_dim))
    hn = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    if "moe" in p:
        y, _ = M.moe_forward(p["moe"], cfg, hn)
    else:
        y = L.mlp(p["mlp"], cfg, hn)
    return h + y, ck, cv


# ==========================================================================
# Decoder-only model (dense / moe / vlm)
# ==========================================================================

def init_decoder_model(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    Vp = cfg.padded_vocab()
    k_dense = cfg.first_k_dense
    p = {
        "embed": L.init_embedding(ks[0], Vp, cfg.d_model, dtype=dtype),
        "layers": stacked_init(
            lambda k: init_decoder_layer(k, cfg, dtype=dtype), ks[1],
            cfg.n_layers - k_dense),
        "final_norm": L.norm_init(cfg.d_model, dtype=dtype),
    }
    if k_dense:
        p["dense_layers"] = stacked_init(
            lambda k: init_decoder_layer(k, cfg, dtype=dtype, dense=True),
            ks[3], k_dense)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.init_embedding(ks[2], Vp, cfg.d_model, dtype=dtype)
    return p


def _logits(p, cfg, h):
    head = p.get("lm_head", p["embed"])
    logits = L.unembed(head, h)
    if cfg.padded_vocab() != cfg.vocab_size:
        neg = jnp.asarray(-1e9, logits.dtype)
        pad = jnp.arange(cfg.padded_vocab()) >= cfg.vocab_size
        logits = jnp.where(pad, neg, logits)
    return logits


def _embed_inputs(p, cfg, batch):
    """Token embeddings, with VLM patch embeddings prepended (stub frontend)."""
    h = L.embed(p["embed"], batch["tokens"])
    if cfg.family == "vlm" and "patch_embeds" in batch:
        h = jnp.concatenate([batch["patch_embeds"].astype(h.dtype), h], axis=1)
    return h


def decoder_forward(p, cfg, batch, *, remat: bool = False,
                    remat_policy: Optional[str] = None):
    """Training/scoring forward. Returns (logits, aux_loss)."""
    h = _embed_inputs(p, cfg, batch)
    window = cfg.sliding_window

    def body(h, p_l):
        h, aux = decoder_layer_fwd(p_l, cfg, h, window=window)
        return h, aux

    h, auxs = layer_scan(_maybe_remat(body, remat, remat_policy), h, p["layers"])
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    n_img = cfg.n_image_patches if cfg.family == "vlm" else 0
    if n_img and h.shape[1] > n_img:
        h = h[:, n_img:]
    return _logits(p, cfg, h), jnp.sum(auxs)


def decoder_prefill(p, cfg, batch, cache):
    """Fill cache from a prompt; returns (last-token logits, cache)."""
    h = _embed_inputs(p, cfg, batch)
    window = cfg.decode_window()

    def body(h, xs):
        p_l, ck, cv = xs
        h, ck, cv = decoder_layer_prefill(p_l, cfg, h, ck, cv, window=window)
        return h, (ck, cv)

    h, (ck, cv) = layer_scan(body, h, (p["layers"], cache["k"], cache["v"]))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h[:, -1:]), {"k": ck, "v": cv}


def _decoder_prefill_chunk_bucket(q, ck, cv, slot_idx, pos0, take, *,
                                  window=None, kv_width=None):
    """Gather each chunk row's bucketed cache window and attend it.

    Attention is bounded to ``kv_width`` cache lines (a static bucket
    >= max(pos0 + take)) instead of the full pool — chunk c costs
    O(S * kv_width) — and runs through the ragged dispatch: the Pallas
    ragged chunked-prefill kernel under ``use_pallas()``, the jnp twin
    (``layers.ragged_prefill_attention``) otherwise.
    """
    w = kv_width if kv_width is not None else ck.shape[1]
    ckg = jnp.take(ck[:, :w], slot_idx, axis=0)
    cvg = jnp.take(cv[:, :w], slot_idx, axis=0)
    return L._dispatch_attention(q, ckg.astype(q.dtype), cvg.astype(q.dtype),
                                 causal=True, window=window, q_offset=pos0,
                                 take=take)


def decoder_layer_prefill_chunk(p_l, cfg, h, ck, cv, slot_idx, positions,
                                pos0, take, *, window=None, kv_width=None):
    """Chunked-prefill layer step writing this layer's slot-pooled cache.

    h [G, S, D] chunk activations; ck/cv [B, M, KV, hd] — the FULL slot
    pool, not a per-request cache. Row ``g`` occupies pool row
    ``slot_idx[g]`` with its chunk starting at absolute offset
    ``pos0[g]``; only its first ``take[g]`` tokens are real (the rest is
    right-padding whose K/V lines are never attended: causal masking at
    per-row offsets keeps every valid query inside its own written span,
    and decode later masks by ``valid_len``). Attention runs against the
    row's full cache lines so later chunks see all earlier ones.
    """
    hn = L.rms_norm(p_l["ln1"], h, cfg.norm_eps)
    q, k, v = L.attn_qkv(p_l["attn"], cfg, hn, positions=positions)
    ck, cv = KV.write_chunk(ck, cv,
                            KV.expand_kv_for_cache(cfg, k).astype(ck.dtype),
                            KV.expand_kv_for_cache(cfg, v).astype(cv.dtype),
                            slot_idx, pos0, take)
    out = _decoder_prefill_chunk_bucket(q, ck, cv, slot_idx, pos0, take,
                                        window=window, kv_width=kv_width)
    g_, s_ = h.shape[:2]
    h = h + L.dense(p_l["attn"]["wo"], out.reshape(g_, s_, cfg.q_dim))
    hn = L.rms_norm(p_l["ln2"], h, cfg.norm_eps)
    # dense layers only (model.chunked_prefill_ok): capacity-routed moe
    # is excluded because expert-capacity competition couples batch rows,
    # which would break the token-identity guarantee of this path
    y = L.mlp(p_l["mlp"], cfg, hn)
    return h + y, ck, cv


def decoder_prefill_chunk(p, cfg, tokens, cache, slot_idx, pos0, take,
                          kv_width=None):
    """Batched ragged chunked prefill for dense decoders.

    tokens [G, S] right-padded chunk ids; slot_idx [G] cache-pool rows;
    pos0 [G] absolute position of each row's tokens[:, 0]; take [G] valid
    token count per row (1 <= take <= S). KV lines land directly in the
    pooled ``cache`` (no per-request allocation/copy). ``kv_width`` — a
    static bound >= max(pos0 + take) — limits attention to that many
    cache lines instead of the whole pool. Returns (logits [G, 1, V] at
    each row's last valid token, cache) — the logits are only meaningful
    for rows whose prompt ends in this chunk.
    """
    h = L.embed(p["embed"], tokens)
    window = cfg.decode_window()
    S = tokens.shape[1]
    positions = pos0[:, None] + jnp.arange(S)[None, :]

    def body(h, xs):
        p_l, ck, cv = xs
        h, ck, cv = decoder_layer_prefill_chunk(
            p_l, cfg, h, ck, cv, slot_idx, positions, pos0, take,
            window=window, kv_width=kv_width)
        return h, (ck, cv)

    h, (ck, cv) = layer_scan(body, h, (p["layers"], cache["k"], cache["v"]))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    last = jnp.take_along_axis(h, (take - 1)[:, None, None], axis=1)
    return _logits(p, cfg, last), {"k": ck, "v": cv}


def decoder_decode(p, cfg, token, pos, cache):
    """token [B,1]; pos [B]. Returns (logits [B,1,V], cache).

    The layer scan carries the whole cache: each layer scatters its B new
    lines at (layer, row, slot) of the donated pool, so a step writes
    B x L lines and copies no layer and no cache."""
    h = L.embed(p["embed"], token)
    window = cfg.decode_window()

    def body(carry, xs):
        h, ck, cv = carry
        p_l, layer = xs
        h, ck, cv = decoder_layer_decode(p_l, cfg, h, ck, cv, layer, pos,
                                         window=window)
        return (h, ck, cv), None

    (h, ck, cv), _ = layer_scan(body, (h, cache["k"], cache["v"]),
                                (p["layers"], jnp.arange(cfg.n_layers)))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), {"k": ck, "v": cv}


# ==========================================================================
# Latent-attention decoder (MLA, DeepSeek-V2): ``first_k_dense`` dense
# layers (``params["dense_layers"]``), then dropless expert layers
# (``params["layers"]``), each stack one layer scan. The cache is the
# latent pool of ``kvcache.init_latent_cache``. The attention part of
# every layer runs under ``jax.named_scope("mla")`` and the expert part
# (router, held experts, shared expert) under ``jax.named_scope("moe")``,
# so their device ops carry the scope in a profile.
# ==========================================================================

_BANKS = ("w_gate", "w_up", "w_down")


def _scan_latent(p, cfg, body, carry, wrap=lambda f: f):
    """Run ``body(carry, p_l, layer, ffn) -> carry`` over every layer in
    order, one layer scan per stack (the dense layers, then the expert
    layers). ``layer`` is the layer's index in the whole model (the
    cache's); ``ffn(h, rows=None)`` is its residual FFN step
    (:func:`_latent_ffn`). The held-expert banks stay out of the scan's
    slices: the expert layer indexes the stacked banks by layer inside
    the ``moe`` scope, so the device work of taking a layer's banks is
    counted as expert work in a profile. ``wrap`` wraps the scan step
    (remat)."""
    stacks = [(p["layers"], cfg.first_k_dense)]
    if cfg.first_k_dense:
        stacks.insert(0, (p["dense_layers"], 0))
    for stack, first in stacks:
        banks = None
        if "moe" in stack:
            moe = stack["moe"]
            banks = {k: moe[k] for k in _BANKS}
            stack = dict(stack, moe={k: v for k, v in moe.items()
                                     if k not in _BANKS})
        n = jax.tree.leaves(stack)[0].shape[0]

        def step(c, xs, banks=banks, first=first):
            p_l, i = xs
            return body(c, p_l, first + i,
                        partial(_latent_ffn, p_l, cfg, banks, i)), None

        carry, _ = layer_scan(wrap(step), carry, (stack, jnp.arange(n)))
    return carry


def _latent_ffn(p_l, cfg, banks, i, h, rows=None):
    """Residual FFN step: (h + ffn(norm(h)), picks per held expert of the
    rows ``rows`` marks, or None for a dense layer). ``banks`` are the
    stack's expert banks [layers, held, ...], ``i`` the layer's index in
    the stack."""
    hn = L.rms_norm(p_l["ln2"], h, cfg.norm_eps)
    if banks is None:
        return h + L.mlp(p_l["mlp"], cfg, hn), None
    with jax.named_scope("moe"):
        pm = dict(p_l["moe"], **{
            k: jax.lax.dynamic_index_in_dim(b, i, 0, keepdims=False)
            for k, b in banks.items()})
        y, picks = M.moe_dropless(pm, cfg, hn, rows)
    return h + y, picks


def latent_forward(p, cfg, batch, *, remat: bool = False,
                   remat_policy: Optional[str] = None):
    """Training/scoring forward. Returns (logits, aux_loss = 0)."""
    h = L.embed(p["embed"], batch["tokens"])
    b, s = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    pos0 = jnp.zeros((b,), jnp.int32)

    def body(h, p_l, layer, ffn):
        a = p_l["attn"]
        with jax.named_scope("mla"):
            qn, qp, ckv, kpe = MLA.project(
                a, cfg, L.rms_norm(p_l["ln1"], h, cfg.norm_eps), positions)
            h = h + L.dense(a["wo"], MLA.attend_lines(a, cfg, qn, qp, ckv,
                                                      kpe, pos0))
        return ffn(h)[0]

    h = _scan_latent(p, cfg, body, h,
                     lambda f: _maybe_remat(f, remat, remat_policy))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), jnp.zeros((), jnp.float32)


def latent_prefill_chunk(p, cfg, tokens, cache, slot_idx, pos0, take,
                         kv_width=None):
    """Batched ragged chunked prefill into the latent pool, as
    :func:`decoder_prefill_chunk` for dense decoders. The layer scans
    carry the layer-stacked pool: each layer scatters the chunk's latent
    lines at (layer, row, line) in place, then attends the first
    ``kv_width`` lines of each row's slot, decompressed to per-head K
    and V. Returns (logits [G, 1, V] at each row's last valid token,
    cache)."""
    h = L.embed(p["embed"], tokens)
    S = tokens.shape[1]
    positions = pos0[:, None] + jnp.arange(S)[None, :]
    w = kv_width if kv_width is not None else cache["ckv"].shape[2]

    def body(carry, p_l, layer, ffn):
        h, ckv, kpe = carry
        a = p_l["attn"]
        with jax.named_scope("mla"):
            qn, qp, c, r = MLA.project(
                a, cfg, L.rms_norm(p_l["ln1"], h, cfg.norm_eps), positions)
            ckv, kpe = KV.write_chunk(ckv, kpe, c.astype(ckv.dtype),
                                      r.astype(kpe.dtype), slot_idx, pos0,
                                      take, layer=layer)

            def lines(pool):
                own = jax.lax.dynamic_index_in_dim(pool, layer, 0,
                                                   keepdims=False)
                return jnp.take(own[:, :w], slot_idx, axis=0).astype(qn.dtype)

            out = MLA.attend_lines(a, cfg, qn, qp, lines(ckv), lines(kpe),
                                   pos0, take)
            h = h + L.dense(a["wo"], out)
        return ffn(h)[0], ckv, kpe

    h, ckv, kpe = _scan_latent(p, cfg, body, (h, cache["ckv"], cache["kpe"]))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    last = jnp.take_along_axis(h, (take - 1)[:, None, None], axis=1)
    return _logits(p, cfg, last), {"ckv": ckv, "kpe": kpe}


def latent_decode(p, cfg, token, pos, cache, live=None):
    """token [B,1]; pos [B]; live [B] (nonzero for rows that serve a
    request; every row where None). Returns (logits [B,1,V], cache,
    load).

    Each layer writes its B latent lines into the carried pool in place
    and attends in the absorbed form (``mla.attend_absorbed``): it reads
    the stored latent lines and never expands per-head K/V. ``load`` is,
    for an expert stack, [held + 1] int32 summed over the expert layers
    and counted over the live rows only: the (token, expert) pairs each
    held expert computed, then the count of held experts that computed
    any; None for a stack without experts.
    """
    h = L.embed(p["embed"], token)
    positions = pos[:, None]
    kvl = KV.valid_len(pos, cache["ckv"].shape[2], None)
    n = cfg.experts_held
    rows = None if live is None else live > 0

    def body(carry, p_l, layer, ffn):
        h, ckv, kpe, load = carry
        a = p_l["attn"]
        with jax.named_scope("mla"):
            qn, qp, c, r = MLA.project(
                a, cfg, L.rms_norm(p_l["ln1"], h, cfg.norm_eps), positions)
            ckv, kpe = KV.write_decode(ckv, kpe, c.astype(ckv.dtype),
                                       r.astype(kpe.dtype), (layer, pos),
                                       None)

            def lines(pool):
                return jax.lax.dynamic_index_in_dim(
                    pool, layer, 0, keepdims=False).astype(qn.dtype)

            out = MLA.attend_absorbed(a, cfg, qn, qp, lines(ckv), lines(kpe),
                                      kvl)
            h = h + L.dense(a["wo"], out)
        h, picks = ffn(h, rows)
        if picks is not None:
            load = load + jnp.concatenate(
                [picks, jnp.sum(picks > 0, dtype=jnp.int32)[None]])
        return h, ckv, kpe, load

    h, ckv, kpe, load = _scan_latent(
        p, cfg, body, (h, cache["ckv"], cache["kpe"],
                       jnp.zeros((n + 1,), jnp.int32)))
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return (_logits(p, cfg, h), {"ckv": ckv, "kpe": kpe},
            load if cfg.family == "moe" else None)


# ==========================================================================
# Encoder-decoder (whisper)
# ==========================================================================

def init_enc_layer(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    return {
        "ln1": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "attn": L.init_attn(ks[0], cfg, dtype=dtype),
        "ln2": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "mlp": L.init_mlp(ks[1], cfg, dtype=dtype),
    }


def init_dec_layer(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "ln1": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "self_attn": L.init_attn(ks[0], cfg, dtype=dtype),
        "ln2": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "cross_attn": L.init_attn(ks[1], cfg, dtype=dtype, cross=True),
        "ln3": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "mlp": L.init_mlp(ks[2], cfg, dtype=dtype),
    }


def init_encdec_model(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    Vp = cfg.padded_vocab()
    return {
        "embed": L.init_embedding(ks[0], Vp, cfg.d_model, dtype=dtype),
        "enc_layers": stacked_init(
            lambda k: init_enc_layer(k, cfg, dtype=dtype), ks[1], cfg.n_encoder_layers),
        "enc_norm": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "dec_layers": stacked_init(
            lambda k: init_dec_layer(k, cfg, dtype=dtype), ks[2], cfg.n_layers),
        "dec_norm": L.norm_init(cfg.d_model, bias=True, dtype=dtype),
        "lm_head": L.init_embedding(ks[3], Vp, cfg.d_model, dtype=dtype),
    }


def encode(p, cfg, frames):
    """frames [B, enc_seq, D] (stub conv frontend output) -> memory."""
    def body(h, p_l):
        hn = L.layer_norm(p_l["ln1"], h, cfg.norm_eps)
        h = h + L.self_attention_block(p_l["attn"], cfg, hn, causal=False)
        hn = L.layer_norm(p_l["ln2"], h, cfg.norm_eps)
        return h + L.mlp(p_l["mlp"], cfg, hn), None

    h, _ = layer_scan(body, frames, p["enc_layers"])
    return L.layer_norm(p["enc_norm"], h, cfg.norm_eps)


def _dec_layer(p_l, cfg, h, memory, *, self_fn):
    hn = L.layer_norm(p_l["ln1"], h, cfg.norm_eps)
    h, extra = self_fn(p_l["self_attn"], hn)
    hn = L.layer_norm(p_l["ln2"], h, cfg.norm_eps)
    h = h + L.cross_attention_block(p_l["cross_attn"], cfg, hn, memory)
    hn = L.layer_norm(p_l["ln3"], h, cfg.norm_eps)
    return h + L.mlp(p_l["mlp"], cfg, hn), extra


def encdec_forward(p, cfg, batch, **_):
    memory = encode(p, cfg, batch["frames"])
    h = L.embed(p["embed"], batch["tokens"])

    def body(h, p_l):
        def self_fn(pa, hn):
            return h + L.self_attention_block(pa, cfg, hn, causal=True), None
        h, _ = _dec_layer(p_l, cfg, h, memory, self_fn=self_fn)
        return h, None

    h, _ = layer_scan(body, h, p["dec_layers"])
    h = L.layer_norm(p["dec_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), jnp.zeros((), jnp.float32)


def encdec_prefill(p, cfg, batch, cache):
    """Encode + prefill decoder self-attn cache + cache cross-attn k/v."""
    memory = encode(p, cfg, batch["frames"])
    h = L.embed(p["embed"], batch["tokens"])

    def body(h, xs):
        p_l, ck, cv = xs

        def self_fn(pa, hn):
            q, k, v = L.attn_qkv(pa, cfg, hn)
            out = L.attention(q, k, v, causal=True)
            b, s = hn.shape[:2]
            nck, ncv = KV.write_prefill(ck, cv, k.astype(ck.dtype),
                                        v.astype(cv.dtype), None)
            return h + L.dense(pa["wo"], out.reshape(b, s, cfg.q_dim)), (nck, ncv)

        h, (nck, ncv) = _dec_layer(p_l, cfg, h, memory, self_fn=self_fn)
        # cache this layer's cross k/v once
        xq, xk, xv = L.attn_qkv(p_l["cross_attn"], cfg, h[:, :1], kv_x=memory,
                                rope=False)
        return h, (nck, ncv, xk.astype(ck.dtype), xv.astype(cv.dtype))

    h, (ck, cv, xk, xv) = layer_scan(body, h, (p["dec_layers"], cache["k"], cache["v"]))
    h = L.layer_norm(p["dec_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h[:, -1:]), {"k": ck, "v": cv, "xk": xk, "xv": xv}


def encdec_decode(p, cfg, token, pos, cache):
    h = L.embed(p["embed"], token)

    def body(h, xs):
        p_l, ck, cv, xk, xv = xs
        hn = L.layer_norm(p_l["ln1"], h, cfg.norm_eps)
        q, k, v = L.attn_qkv(p_l["self_attn"], cfg, hn, positions=pos[:, None])
        nck, ncv = KV.write_decode(ck, cv, k.astype(ck.dtype), v.astype(cv.dtype),
                                   pos, None)
        kvl = KV.valid_len(pos, nck.shape[1], None)
        out = L._dispatch_attention(q, nck.astype(q.dtype),
                                    ncv.astype(q.dtype), causal=False,
                                    window=None, kv_len=kvl)
        b = h.shape[0]
        h = h + L.dense(p_l["self_attn"]["wo"], out.reshape(b, 1, cfg.q_dim))
        # cross-attn against cached encoder k/v
        hn = L.layer_norm(p_l["ln2"], h, cfg.norm_eps)
        xq = L.dense(p_l["cross_attn"]["wq"], hn).reshape(
            b, 1, cfg.n_heads, cfg.resolved_head_dim)
        out = L.attention(xq, xk.astype(xq.dtype), xv.astype(xq.dtype),
                          causal=False)
        h = h + L.dense(p_l["cross_attn"]["wo"], out.reshape(b, 1, cfg.q_dim))
        hn = L.layer_norm(p_l["ln3"], h, cfg.norm_eps)
        h = h + L.mlp(p_l["mlp"], cfg, hn)
        return h, (nck, ncv)

    h, (ck, cv) = layer_scan(body, h, (p["dec_layers"], cache["k"], cache["v"],
                                         cache["xk"], cache["xv"]))
    h = L.layer_norm(p["dec_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}


# ==========================================================================
# Hybrid (zamba2): Mamba2 stack + ONE shared attention block every N layers
# ==========================================================================

def init_hybrid_model(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    Vp = cfg.padded_vocab()
    shared = {
        "ln1": L.norm_init(cfg.d_model, dtype=dtype),
        "attn": L.init_attn(ks[0], cfg, dtype=dtype),
        "ln2": L.norm_init(cfg.d_model, dtype=dtype),
        "mlp": L.init_mlp(ks[1], cfg, dtype=dtype),
    }
    return {
        "embed": L.init_embedding(ks[2], Vp, cfg.d_model, dtype=dtype),
        "mamba": stacked_init(
            lambda k: {"ln": L.norm_init(cfg.d_model, dtype=dtype),
                       "m": S.init_mamba2(k, cfg, dtype=dtype)},
            ks[3], cfg.n_layers),
        "shared_attn": shared,
        "final_norm": L.norm_init(cfg.d_model, dtype=dtype),
        "lm_head": L.init_embedding(ks[4], Vp, cfg.d_model, dtype=dtype),
    }


def _hybrid_segments(cfg):
    """Yield (start, stop) mamba segments; shared attn runs after each full one."""
    segs = []
    i = 0
    while i < cfg.n_layers:
        j = min(i + cfg.attn_every, cfg.n_layers)
        segs.append((i, j))
        i = j
    return segs


def _shared_attn_block(p, cfg, h, *, mode, cache=None, pos=None):
    hn = L.rms_norm(p["ln1"], h, cfg.norm_eps)
    window = cfg.decode_window()
    if mode == "full":
        h = h + L.self_attention_block(p["attn"], cfg, hn, causal=True,
                                       window=cfg.sliding_window)
        new_cache = None
    elif mode == "prefill":
        q, k, v = L.attn_qkv(p["attn"], cfg, hn)
        out = L.full_attention(q, k, v, cfg, causal=True, window=window)
        b, s = h.shape[:2]
        h = h + L.dense(p["attn"]["wo"], out.reshape(b, s, cfg.q_dim))
        ck, cv = KV.write_prefill(cache["k"], cache["v"], k.astype(cache["k"].dtype),
                                  v.astype(cache["v"].dtype), window)
        new_cache = {"k": ck, "v": cv}
    else:  # decode
        q, k, v = L.attn_qkv(p["attn"], cfg, hn, positions=pos[:, None])
        ck, cv = KV.write_decode(cache["k"], cache["v"], k.astype(cache["k"].dtype),
                                 v.astype(cache["v"].dtype), pos, window)
        kvl = KV.valid_len(pos, ck.shape[1], window)
        out = L._dispatch_attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                                    causal=False, window=None, kv_len=kvl)
        b = h.shape[0]
        h = h + L.dense(p["attn"]["wo"], out.reshape(b, 1, cfg.q_dim))
        new_cache = {"k": ck, "v": cv}
    hn = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    return h + L.mlp(p["mlp"], cfg, hn), new_cache


def _tree_slice(tree, a, b):
    return jax.tree.map(lambda x: x[a:b], tree)


def hybrid_forward(p, cfg, batch, *, remat=False, remat_policy=None, **_):
    h = L.embed(p["embed"], batch["tokens"])

    def body(h, p_l):
        hn = L.rms_norm(p_l["ln"], h, cfg.norm_eps)
        y, _ = S.mamba2_forward(p_l["m"], cfg, hn)
        return h + y, None

    body = _maybe_remat(body, remat, remat_policy)
    for (a, b) in _hybrid_segments(cfg):
        h, _ = layer_scan(body, h, _tree_slice(p["mamba"], a, b))
        h, _ = _shared_attn_block(p["shared_attn"], cfg, h, mode="full")
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), jnp.zeros((), jnp.float32)


def hybrid_init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    n_seg = len(_hybrid_segments(cfg))
    mc = S.mamba2_init_cache(cfg, batch, dtype)
    return {
        "mamba": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape).copy(), mc),
        "attn": {
            "k": jnp.zeros((n_seg, batch, max_len, cfg.n_kv_heads,
                            cfg.resolved_head_dim), dtype),
            "v": jnp.zeros((n_seg, batch, max_len, cfg.n_kv_heads,
                            cfg.resolved_head_dim), dtype),
        },
    }


def _hybrid_stage(p, cfg, h, cache, *, mode, pos=None):
    def body(h, xs):
        p_l, c_l = xs
        hn = L.rms_norm(p_l["ln"], h, cfg.norm_eps)
        y, nc = S.mamba2_forward(p_l["m"], cfg, hn, initial=c_l)
        return h + y, nc

    new_mamba, new_attn = [], []
    for si, (a, b) in enumerate(_hybrid_segments(cfg)):
        h, nc = layer_scan(body, h, (_tree_slice(p["mamba"], a, b),
                                       _tree_slice(cache["mamba"], a, b)))
        new_mamba.append(nc)
        ac = jax.tree.map(lambda x: x[si], cache["attn"])
        h, nac = _shared_attn_block(p["shared_attn"], cfg, h, mode=mode,
                                    cache=ac, pos=pos)
        new_attn.append(nac)
    new_cache = {
        "mamba": jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *new_mamba),
        "attn": jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *new_attn),
    }
    return h, new_cache


def hybrid_prefill(p, cfg, batch, cache):
    h = L.embed(p["embed"], batch["tokens"])
    h, cache = _hybrid_stage(p, cfg, h, cache, mode="prefill")
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h[:, -1:]), cache


def hybrid_decode(p, cfg, token, pos, cache):
    h = L.embed(p["embed"], token)
    h, cache = _hybrid_stage(p, cfg, h, cache, mode="decode", pos=pos)
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), cache


# ==========================================================================
# xLSTM (ssm family): groups of (mlstm_per_slstm mLSTM + 1 sLSTM)
# ==========================================================================

def _xlstm_groups(cfg) -> Tuple[int, int]:
    per = cfg.mlstm_per_slstm + 1
    n_groups = max(cfg.n_layers // per, 1)
    return n_groups, cfg.mlstm_per_slstm


def init_xlstm_model(key, cfg, *, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    Vp = cfg.padded_vocab()
    n_groups, m_per = _xlstm_groups(cfg)

    def init_group(k):
        k1, k2 = jax.random.split(k)
        return {
            "mlstm": stacked_init(
                lambda kk: {"ln": L.norm_init(cfg.d_model, dtype=dtype),
                            "m": S.init_mlstm(kk, cfg, dtype=dtype)}, k1, m_per),
            "slstm": {"ln": L.norm_init(cfg.d_model, dtype=dtype),
                      "s": S.init_slstm(k2, cfg, dtype=dtype)},
        }

    return {
        "embed": L.init_embedding(ks[0], Vp, cfg.d_model, dtype=dtype),
        "groups": stacked_init(init_group, ks[1], n_groups),
        "final_norm": L.norm_init(cfg.d_model, dtype=dtype),
        "lm_head": L.init_embedding(ks[2], Vp, cfg.d_model, dtype=dtype),
    }


def _xlstm_group_apply(p_g, cfg, h, cache_g, *, decode: bool):
    _, m_per = _xlstm_groups(cfg)
    new_m = []
    for i in range(m_per):
        p_l = jax.tree.map(lambda x: x[i], p_g["mlstm"])
        hn = L.rms_norm(p_l["ln"], h, cfg.norm_eps)
        c = None if cache_g is None else jax.tree.map(lambda x: x[i], cache_g["mlstm"])
        fn = S.mlstm_decode if decode else S.mlstm_forward
        y, nc = fn(p_l["m"], cfg, hn, c) if decode else fn(p_l["m"], cfg, hn, initial=c)
        h = h + y
        new_m.append(nc)
    hn = L.rms_norm(p_g["slstm"]["ln"], h, cfg.norm_eps)
    c = None if cache_g is None else cache_g["slstm"]
    if decode:
        y, ns = S.slstm_decode(p_g["slstm"]["s"], cfg, hn, c)
    else:
        y, ns = S.slstm_forward(p_g["slstm"]["s"], cfg, hn, initial=c)
    h = h + y
    new_cache = {"mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *new_m),
                 "slstm": ns}
    return h, new_cache


def xlstm_apply(p, cfg, h, cache=None, *, decode=False):
    def body(h, xs):
        p_g, c_g = xs
        return _xlstm_group_apply(p_g, cfg, h, c_g, decode=decode)

    if cache is None:
        n_groups, _ = _xlstm_groups(cfg)
        # build a dummy cache pytree so scan has uniform xs
        c0 = xlstm_init_cache(cfg, h.shape[0], 0, h.dtype)
        h, new_cache = layer_scan(body, h, (p["groups"], c0))
    else:
        h, new_cache = layer_scan(body, h, (p["groups"], cache))
    return h, new_cache


def xlstm_init_cache(cfg, batch: int, max_len: int = 0, dtype=jnp.float32):
    n_groups, m_per = _xlstm_groups(cfg)
    mc = S.mlstm_init_cache(cfg, batch, dtype)
    sc = S.slstm_init_cache(cfg, batch, dtype)
    g = {
        "mlstm": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (m_per,) + x.shape).copy(), mc),
        "slstm": sc,
    }
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape).copy(), g)


def xlstm_forward(p, cfg, batch, **_):
    h = L.embed(p["embed"], batch["tokens"])
    h, _ = xlstm_apply(p, cfg, h)
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), jnp.zeros((), jnp.float32)


def xlstm_prefill(p, cfg, batch, cache):
    h = L.embed(p["embed"], batch["tokens"])
    h, cache = xlstm_apply(p, cfg, h, cache)
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h[:, -1:]), cache


def xlstm_decode(p, cfg, token, pos, cache):
    h = L.embed(p["embed"], token)
    h, cache = xlstm_apply(p, cfg, h, cache, decode=True)
    h = L.rms_norm(p["final_norm"], h, cfg.norm_eps)
    return _logits(p, cfg, h), cache
