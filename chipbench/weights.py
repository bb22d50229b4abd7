"""Seeded random weights of a dense GQA decoder, made on the device.

The benchmark owns its weights: ``canonical`` draws every tensor of a
configuration from the seed in one jitted program, in the dtype the model
is served in. The served program receives them through ``to_program`` (in
``drive_common``), and ``reference.py`` draws them again from the same
seed after the served program is freed, so the reference takes nothing
that the program made.

Canonical tensors (layers stacked on the leading axis ``L``)::

    embed [V, D]          lm_head [V, D]        final_norm [D]
    ln1, ln2 [L, D]       wq [L, D, H*hd]       bq [L, H*hd]
    wk, wv [L, D, K*hd]   bk, bv [L, K*hd]      wo [L, H*hd, D]
    w_gate, w_up [L, D, F]                      w_down [L, F, D]

This module imports nothing of the program.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    norm_eps: float
    # token ids whose row of the output head is zero, so that their logit
    # is 0 and random weights never make them the greedy token
    silent: tuple = ()

    @classmethod
    def of(cls, model: dict) -> "Sizes":
        """Sizes from a ``configs/<config>.json`` (keys as published)."""
        return cls(layers=int(model["num_hidden_layers"]),
                   d_model=int(model["hidden_size"]),
                   heads=int(model["num_attention_heads"]),
                   kv_heads=int(model["num_key_value_heads"]),
                   head_dim=int(model["head_dim"]),
                   d_ff=int(model["intermediate_size"]),
                   vocab=int(model["vocab_size"]),
                   qkv_bias=bool(model["qkv_bias"]),
                   tied=bool(model["tie_word_embeddings"]),
                   rope_theta=float(model["rope_theta"]),
                   norm_eps=float(model["rms_norm_eps"]),
                   silent=tuple(int(i) for i in
                                model.get("silent_head_rows", ())))

    def shapes(self) -> dict:
        L, D, F, V = self.layers, self.d_model, self.d_ff, self.vocab
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        s = {"embed": (V, D), "final_norm": (D,), "ln1": (L, D),
             "ln2": (L, D), "wq": (L, D, q), "wk": (L, D, kv),
             "wv": (L, D, kv), "wo": (L, q, D), "w_gate": (L, D, F),
             "w_up": (L, D, F), "w_down": (L, F, D)}
        if not self.tied:
            s["lm_head"] = (V, D)
        if self.qkv_bias:
            s.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
        return s


def seed_words(seed: int, stream: int = 0) -> np.ndarray:
    """A seed of any size (and a stream index) as uint32 words, passed to
    the jitted generator as data so that every seed shares one program."""
    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                       stream], np.uint32)


def _scale(name: str, shape) -> "tuple[float, float]":
    """(mean, std) of each tensor's entries."""
    if name in ("embed", "lm_head"):
        return 0.0, 0.02
    if name in ("ln1", "ln2", "final_norm"):
        return 1.0, 0.05
    if name in ("bq", "bk", "bv"):
        return 0.0, 0.02
    return 0.0, 1.0 / float(np.sqrt(shape[-2]))


def draw(sizes: Sizes, words, dtype) -> dict:
    """Every canonical tensor, traced (call under ``jax.jit``)."""
    key = jax.random.PRNGKey(0)
    for i in range(3):
        key = jax.random.fold_in(key, words[i])
    out = {}
    for j, (name, shape) in enumerate(sorted(sizes.shapes().items())):
        mean, std = _scale(name, shape)
        x = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        out[name] = (mean + std * x).astype(dtype)
    if sizes.silent:
        head = "embed" if sizes.tied else "lm_head"
        out[head] = out[head].at[jnp.asarray(sizes.silent)].set(0)
    return out


@partial(jax.jit, static_argnums=(0, 2))
def canonical(sizes: Sizes, words, dtype=jnp.bfloat16) -> dict:
    """The canonical tensors of one model in ``dtype``: one program."""
    return draw(sizes, words, dtype)
