"""Family ``dense``: a dense GQA decoder with full causal attention
(Qwen2, InternLM2), bound to ``weights.py`` and ``reference.py``.

Every ``families/<family>.py`` exposes what the harness needs of a model
(``registry.FAMILY_API``):

* ``sizes(model)``: the sizes of a ``configs/<config>.json``, a hashable
  object with at least ``vocab``;
* ``canonical(sizes, words, dtype)``: every weight tensor drawn from the
  seed words in one jitted program, in ``dtype``;
* ``served_config(model)``: the program's ``ModelConfig`` for the file,
  checked against every size the file states;
* ``program_params(cfg, sizes, words)``: the same tensors laid out as the
  program's parameter pytree;
* ``logits_at(sizes, w, ids, want, mode)``: the family's plain reference,
  logits at positions ``want`` of ``ids`` (length a multiple of
  ``Q_BLOCK``), in ``mode`` ``"f32"`` or a control precision.

Optional: ``decode_flops(sizes, ctx_len)``, the operations one decode row
at context ``ctx_len`` needs (read by ``metrics/mfu.decode.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import reference, weights

Q_BLOCK = reference.Q_BLOCK
sizes = weights.Sizes.of
canonical = weights.canonical
logits_at = reference.logits_at

# ModelConfig fields that must equal what the config file states
STATED = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "qkv_bias": "qkv_bias", "tie_embeddings": "tie_word_embeddings",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "dtype": "torch_dtype"}


def served_config(model: dict):
    """The program's ``ModelConfig`` for a config file, checked against
    every size the file states."""
    from repro.configs import get_config
    cfg = get_config(model["arch_id"]).variant(**model.get("overrides", {}))
    for f, key in STATED.items():
        got, want = getattr(cfg, f), model[key]
        if got != want:
            raise ValueError(f"{model['arch_id']}: the program's {f}={got!r} "
                             f"departs from the stated {key}={want!r}")
    if cfg.family != "dense" or cfg.decode_window() is not None:
        raise ValueError(f"{model['arch_id']}: served config is not a "
                         f"full-attention dense decoder as stated")
    return cfg


def program_params(cfg, sizes: weights.Sizes, words):
    """The seeded canonical weights, laid out as the program's params
    pytree (checked against ``init_params``'s shapes)."""
    from repro.models import model as M
    tmpl = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    w = weights.canonical(sizes, words, jnp.dtype(cfg.dtype))
    vp = tmpl["embed"]["w"].shape[0]

    def rows(x):
        return jnp.pad(x, ((0, vp - x.shape[0]), (0, 0)))

    def lin(name):
        d = {"w": w[name]}
        b = "b" + name[1]
        if name in ("wq", "wk", "wv") and b in w:
            d["b"] = w[b]
        return d

    p = {"embed": {"w": rows(w["embed"])},
         "layers": {"ln1": {"scale": w["ln1"]},
                    "attn": {n: lin(n) for n in ("wq", "wk", "wv", "wo")},
                    "ln2": {"scale": w["ln2"]},
                    "mlp": {n: {"w": w[n]}
                            for n in ("w_gate", "w_up", "w_down")}},
         "final_norm": {"scale": w["final_norm"]}}
    if "lm_head" in w:
        p["lm_head"] = {"w": rows(w["lm_head"])}
    want = jax.tree.map(lambda s: (s.shape, s.dtype), tmpl)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), p)
    if want != got:
        raise ValueError(f"weights do not match the program's parameter "
                         f"layout for {cfg.arch_id}")
    del w
    return p


def decode_flops(sz: weights.Sizes, ctx_len: int) -> float:
    """Operations one decode row needs at context ``ctx_len``: every
    projection and the output head (2 per weight), plus Q.K and P.V over
    the ``ctx_len`` lines it attends."""
    q, kv = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    per_layer = sz.d_model * (2 * q + 2 * kv) + 3 * sz.d_model * sz.d_ff
    attn = 2 * 2 * q * ctx_len
    return float(sz.layers * (2 * per_layer + attn)
                 + 2 * sz.vocab * sz.d_model)

