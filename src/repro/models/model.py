"""Public model API: family dispatch + input specs for the dry-run.

Every architecture exposes the same four entry points:
  init_params(cfg, key)                     -> params pytree
  forward(params, cfg, batch)               -> (logits, aux_loss)
  serve_prefill(params, cfg, batch, cache)  -> (last logits, cache)
  serve_decode(params, cfg, token, pos, cache) -> (logits, cache)

Latent-attention configs (``cfg.is_mla``, DeepSeek-V2) take the
``transformer.latent_*`` stack and the latent cache under the same names.

``input_specs(cfg, shape)`` returns jax.ShapeDtypeStruct stand-ins for
every model input of the given benchmark shape (no device allocation) —
this is what launch/dryrun.py lowers against.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, InputShape, SHAPES
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import kvcache as KV


# --------------------------------------------------------------------------
# init / forward dispatch
# --------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key, *, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.family in ("dense", "moe", "vlm"):
        return T.init_decoder_model(key, cfg, dtype=dtype)
    if cfg.family == "audio":
        return T.init_encdec_model(key, cfg, dtype=dtype)
    if cfg.family == "hybrid":
        return T.init_hybrid_model(key, cfg, dtype=dtype)
    if cfg.family == "ssm":
        return T.init_xlstm_model(key, cfg, dtype=dtype)
    raise ValueError(cfg.family)


def forward(params, cfg: ModelConfig, batch: Dict[str, Any], *,
            remat: bool = False, remat_policy: Optional[str] = None):
    if cfg.is_mla:
        return T.latent_forward(params, cfg, batch, remat=remat,
                                remat_policy=remat_policy)
    if cfg.family in ("dense", "moe", "vlm"):
        return T.decoder_forward(params, cfg, batch, remat=remat,
                                 remat_policy=remat_policy)
    if cfg.family == "audio":
        return T.encdec_forward(params, cfg, batch)
    if cfg.family == "hybrid":
        return T.hybrid_forward(params, cfg, batch, remat=remat,
                                remat_policy=remat_policy)
    if cfg.family == "ssm":
        return T.xlstm_forward(params, cfg, batch)
    raise ValueError(cfg.family)


def loss_fn(params, cfg: ModelConfig, batch, **kw):
    logits, aux = forward(params, cfg, batch, **kw)
    ce = L.softmax_cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# serving dispatch
# --------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, dtype=jnp.bfloat16):
    if cfg.is_mla:
        return KV.init_latent_cache(cfg, batch, max_len, dtype)
    if cfg.family in ("dense", "moe", "vlm"):
        return KV.init_attn_cache(cfg, batch, max_len, cfg.n_layers, dtype)
    if cfg.family == "audio":
        c = KV.init_attn_cache(cfg, batch, max_len, cfg.n_layers, dtype)
        hd = cfg.resolved_head_dim
        c["xk"] = jnp.zeros((cfg.n_layers, batch, cfg.encoder_seq,
                             cfg.n_kv_heads, hd), dtype)
        c["xv"] = jnp.zeros_like(c["xk"])
        return c
    if cfg.family == "hybrid":
        return T.hybrid_init_cache(cfg, batch, max_len, dtype)
    if cfg.family == "ssm":
        # recurrent numerics stay f32; the sLSTM hidden state rides in the
        # activation dtype so the layer-scan carry dtype is stable
        return T.xlstm_init_cache(cfg, batch, 0, dtype)
    raise ValueError(cfg.family)


def serve_prefill(params, cfg: ModelConfig, batch, cache):
    if cfg.is_mla:
        b, s = batch["tokens"].shape
        return T.latent_prefill_chunk(
            params, cfg, batch["tokens"], cache, jnp.arange(b, dtype=jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.full((b,), s, jnp.int32))
    if cfg.family in ("dense", "moe", "vlm"):
        return T.decoder_prefill(params, cfg, batch, cache)
    if cfg.family == "audio":
        return T.encdec_prefill(params, cfg, batch, cache)
    if cfg.family == "hybrid":
        return T.hybrid_prefill(params, cfg, batch, cache)
    if cfg.family == "ssm":
        return T.xlstm_prefill(params, cfg, batch, cache)
    raise ValueError(cfg.family)


def chunked_prefill_ok(cfg: ModelConfig) -> bool:
    """Whether prompts can be prefilled as padded/ragged chunked batches
    written straight into the slot-pooled cache. Causal masking keeps
    padded lines out of every valid query, so attention-only decoders
    qualify where no batch row's result depends on another's: dense
    decoders, and latent-attention expert stacks with full attention,
    whose expert layers route dropless by construction
    (``transformer.latent_*`` always calls ``moe.moe_dropless``).
    Capacity-routed experts (mixtral, kimi: tokens over capacity are
    dropped, which couples rows), recurrent families (ssm/hybrid) and
    encoder-decoder/vlm prefixes keep the engine's legacy per-slot path."""
    if cfg.family == "dense":
        return True
    return (cfg.is_mla and cfg.family == "moe"
            and cfg.decode_window() is None)


def serve_prefill_chunk(params, cfg: ModelConfig, tokens, cache, slot_idx,
                        pos0, take, kv_width=None):
    """Batched ragged chunk prefill into the slot-pooled serving cache.

    tokens [G, S] right-padded ids; slot_idx/pos0/take [G]; ``kv_width``
    statically bounds how many cache lines attention reads — see
    ``transformer.decoder_prefill_chunk``. Only configs that
    ``chunked_prefill_ok`` admits support this path.
    """
    if not chunked_prefill_ok(cfg):
        raise NotImplementedError(
            f"chunked slot prefill is not supported for {cfg.arch_id!r}")
    if cfg.is_mla:
        return T.latent_prefill_chunk(params, cfg, tokens, cache, slot_idx,
                                      pos0, take, kv_width=kv_width)
    return T.decoder_prefill_chunk(params, cfg, tokens, cache, slot_idx,
                                   pos0, take, kv_width=kv_width)


def serve_decode_counted(params, cfg: ModelConfig, token, pos, cache,
                         live):
    """``serve_decode`` plus the expert load of the step's ``live`` [B]
    rows: for a dropless expert stack, [held + 1] int32 (pairs per held
    expert, then held experts hit, summed over layers —
    ``transformer.latent_decode``); None for every other config, whose
    program is ``serve_decode``'s."""
    if cfg.is_mla:
        return T.latent_decode(params, cfg, token, pos, cache, live)
    return (*serve_decode(params, cfg, token, pos, cache), None)


def serve_decode(params, cfg: ModelConfig, token, pos, cache):
    if cfg.is_mla:
        return T.latent_decode(params, cfg, token, pos, cache)[:2]
    if cfg.family in ("dense", "moe", "vlm"):
        return T.decoder_decode(params, cfg, token, pos, cache)
    if cfg.family == "audio":
        return T.encdec_decode(params, cfg, token, pos, cache)
    if cfg.family == "hybrid":
        return T.hybrid_decode(params, cfg, token, pos, cache)
    if cfg.family == "ssm":
        return T.xlstm_decode(params, cfg, token, pos, cache)
    raise ValueError(cfg.family)


# convenience aliases used by launch/
def train_step_fn(cfg):  # resolved in training.loop to avoid import cycle
    from repro.training.loop import make_train_step
    return make_train_step(cfg)


def serve_prefill_fn(cfg):
    def fn(params, batch, cache):
        return serve_prefill(params, cfg, batch, cache)
    return fn


def serve_decode_fn(cfg):
    def fn(params, token, pos, cache):
        return serve_decode(params, cfg, token, pos, cache)
    return fn


def build_model(cfg: ModelConfig):
    """Bundle of bound functions for one architecture."""
    return {
        "config": cfg,
        "init": lambda key, dtype=None: init_params(cfg, key, dtype=dtype),
        "forward": lambda p, b, **kw: forward(p, cfg, b, **kw),
        "loss": lambda p, b, **kw: loss_fn(p, cfg, b, **kw),
        "init_cache": lambda b, m, dtype=jnp.bfloat16: init_cache(cfg, b, m, dtype=dtype),
        "prefill": lambda p, b, c: serve_prefill(p, cfg, b, c),
        "decode": lambda p, t, pos, c: serve_decode(p, cfg, t, pos, c),
    }


# --------------------------------------------------------------------------
# input specs (dry-run stand-ins, ShapeDtypeStruct only)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: InputShape | str,
                *, cache_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Abstract inputs for (cfg, shape). For train/prefill: the batch dict.
    For decode: {"token","pos","cache"} with a cache representing a
    prefilled context of shape.seq_len tokens."""
    if isinstance(shape, str):
        shape = SHAPES[shape]
    B, Sq = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def batch_specs(seq):
        b: Dict[str, Any] = {"tokens": _sds((B, seq), i32)}
        if cfg.family == "vlm":
            b["patch_embeds"] = _sds((B, cfg.n_image_patches, cfg.d_model),
                                     jnp.bfloat16)
            b["tokens"] = _sds((B, seq - cfg.n_image_patches), i32)
        if cfg.family == "audio":
            b["frames"] = _sds((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
        return b

    if shape.kind == "train":
        b = batch_specs(Sq)
        lab_seq = b["tokens"].shape[1]
        b["labels"] = _sds((B, lab_seq), i32)
        return b

    if shape.kind == "prefill":
        b = batch_specs(Sq)
        max_len = KV.cache_len(cfg, Sq)
        cache = init_cache_specs(cfg, B, max_len, cache_dtype)
        return {"batch": b, "cache": cache}

    # decode: one new token against a context of Sq tokens
    max_len = KV.cache_len(cfg, Sq)
    return {
        "token": _sds((B, 1), i32),
        "pos": _sds((B,), i32),
        "cache": init_cache_specs(cfg, B, max_len, cache_dtype),
    }


def init_cache_specs(cfg, batch, max_len, dtype):
    """ShapeDtypeStruct mirror of init_cache (no allocation)."""
    concrete = jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, dtype=dtype))
    return concrete
