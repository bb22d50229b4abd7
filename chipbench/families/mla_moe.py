"""Family ``mla_moe``: a decoder with latent attention (MLA) and sparse
experts, as DeepSeek-V2 (arXiv:2405.04434 §2.1-2.2), served as one
chip's share of an expert-parallel deployment.

The plain reference below follows the published equations in
``jax.numpy`` at float32 with every matrix product at
``Precision.HIGHEST``, one sequence at a time, with no cache, no batching
and no kernels, and imports nothing of the program. For hidden states
``h`` at positions ``p``::

    [q_nope | q_pe] = W_q h;  [c_kv | k_pe] = W_kva h;  c_kv = RMSNorm(c_kv)
    q_pe, k_pe = rope(q_pe, p), rope(k_pe, p)      (k_pe one for all heads)
    [k_nope | v] = W_kvb c_kv                      (per head, decompressed)
    s = (q_nope . k_nope + q_pe . k_pe) * (N + R)^-0.5 * m^2, causal
    attn = W_o concat_h(softmax(s) v)

with YaRN's inverse frequencies (a ramp between ``theta^(-2i/R)`` and
that value over the factor, across the correction range of beta_fast /
beta_slow rotations in the original positions) and
``m = 0.1 * mscale_all_dim * ln(factor) + 1``; cos/sin are scaled by the
ratio of the two mscales. Layers before ``first_k_dense_replace`` end in
a SwiGLU of width ``intermediate_size``; the others in::

    g = softmax(W_g h) over every routed expert (float32); top-k greedy,
    renormalised only under norm_topk_prob, times routed_scaling_factor
    y = S(h) + sum over picked i of the held experts g_i E_i(h)

where ``S`` is the shared experts' SwiGLU (width n_shared x moe width)
and ``E_i`` a SwiGLU of width ``moe_intermediate_size``. The experts are
computed by a dense loop over the held ones, each masked by its gate.
Departure from the published model: rope rotates the two halves of
``q_pe``/``k_pe`` (the checkpoint interleaves them; on random weights the
same map up to a column permutation of ``W_q`` and ``W_kva``).

The share: the file's ``n_routed_experts`` counts the experts this chip
holds, ``routed_experts_offset`` the first of them and
``routed_experts_published`` the router's width. Expert ``e``'s weights
are drawn from ``e`` alone, so every share of one seed holds the same
experts as the whole model; the router scores every expert, and what the
experts held elsewhere would add is left out, in the program and here.

Per-layer readers of this family (``metrics/``): ``mla_roofline.decode``
and ``moe_roofline.decode`` take the device time of the decode program's
ops under the ``mla`` and ``moe`` scopes (``decode_scopes``) and the bytes
of ``mla_decode_bytes`` / ``moe_decode_bytes``; the expert counters come
from the engine's ``stats`` (``expert_picks_<i>``, ``experts_hit``).
"""

import functools
import json
import math
import re
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
BF16 = 2          # bytes of a published (bfloat16) value


@dataclass(frozen=True)
class Sizes:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    experts: int              # the router's width
    held: int
    offset: int
    top_k: int
    shared: int
    norm_topk: bool
    scaling: float
    vocab: int
    tied: bool
    rope_theta: float
    yarn: tuple               # (factor, original, beta_fast, beta_slow,
                              #  mscale, mscale_all_dim)
    norm_eps: float
    silent: tuple = ()

    @classmethod
    def of(cls, model: dict) -> "Sizes":
        y = model["rope_scaling"]
        return cls(layers=int(model["num_hidden_layers"]),
                   dense_layers=int(model["first_k_dense_replace"]),
                   d_model=int(model["hidden_size"]),
                   heads=int(model["num_attention_heads"]),
                   kv_rank=int(model["kv_lora_rank"]),
                   nope=int(model["qk_nope_head_dim"]),
                   rope=int(model["qk_rope_head_dim"]),
                   v_dim=int(model["v_head_dim"]),
                   dense_ff=int(model["intermediate_size"]),
                   expert_ff=int(model["moe_intermediate_size"]),
                   experts=int(model["routed_experts_published"]),
                   held=int(model["n_routed_experts"]),
                   offset=int(model["routed_experts_offset"]),
                   top_k=int(model["num_experts_per_tok"]),
                   shared=int(model["n_shared_experts"]),
                   norm_topk=bool(model["norm_topk_prob"]),
                   scaling=float(model["routed_scaling_factor"]),
                   vocab=int(model["vocab_size"]),
                   tied=bool(model["tie_word_embeddings"]),
                   rope_theta=float(model["rope_theta"]),
                   yarn=(float(y["factor"]),
                         int(y["original_max_position_embeddings"]),
                         float(y["beta_fast"]), float(y["beta_slow"]),
                         float(y["mscale"]), float(y["mscale_all_dim"])),
                   norm_eps=float(model["rms_norm_eps"]),
                   silent=tuple(int(i) for i in
                                model.get("silent_head_rows", ())))

    def shapes(self) -> dict:
        """Canonical tensors; expert banks [layers, held, ...]."""
        L, D, V = self.layers, self.d_model, self.vocab
        H, C, N, R, Vh = (self.heads, self.kv_rank, self.nope, self.rope,
                          self.v_dim)
        k, Le = self.dense_layers, self.layers - self.dense_layers
        F, Fs, Fd = self.expert_ff, self.expert_ff * self.shared, \
            self.dense_ff
        s = {"embed": (V, D), "final_norm": (D,), "ln1": (L, D),
             "ln2": (L, D), "wq": (L, D, H * (N + R)),
             "wkv_a": (L, D, C + R), "kv_norm": (L, C),
             "wkv_b": (L, C, H * (N + Vh)), "wo": (L, H * Vh, D),
             "d_gate": (k, D, Fd), "d_up": (k, D, Fd), "d_down": (k, Fd, D),
             "router": (Le, D, self.experts),
             "e_gate": (Le, self.held, D, F), "e_up": (Le, self.held, D, F),
             "e_down": (Le, self.held, F, D),
             "s_gate": (Le, D, Fs), "s_up": (Le, D, Fs),
             "s_down": (Le, Fs, D)}
        if not self.tied:
            s["lm_head"] = (V, D)
        return s


sizes = Sizes.of

EXPERT_BANKS = ("e_gate", "e_up", "e_down")


def _scale(name: str, shape) -> "tuple[float, float]":
    """(mean, std) of each tensor's entries."""
    if name in ("embed", "lm_head"):
        return 0.0, 0.02
    if name in ("ln1", "ln2", "final_norm", "kv_norm"):
        return 1.0, 0.05
    return 0.0, 1.0 / float(np.sqrt(shape[-2]))


def draw(sz: Sizes, words, dtype) -> dict:
    """Every canonical tensor, traced (call under ``jax.jit``). Expert
    ``e`` of each layer is drawn from (tensor, e) alone."""
    key = jax.random.PRNGKey(0)
    for i in range(3):
        key = jax.random.fold_in(key, words[i])
    out = {}
    ids = sz.offset + jnp.arange(sz.held)
    for j, (name, shape) in enumerate(sorted(sz.shapes().items())):
        mean, std = _scale(name, shape)
        kj = jax.random.fold_in(key, j)
        if name in EXPERT_BANKS:
            one = (shape[0],) + shape[2:]
            x = jax.vmap(lambda e: jax.random.normal(
                jax.random.fold_in(kj, e), one, jnp.float32), out_axes=1)(ids)
        else:
            x = jax.random.normal(kj, shape, jnp.float32)
        out[name] = (mean + std * x).astype(dtype)
    if sz.silent:
        head = "embed" if sz.tied else "lm_head"
        out[head] = out[head].at[jnp.asarray(sz.silent)].set(0)
    return out


@partial(jax.jit, static_argnums=(0, 2))
def canonical(sz: Sizes, words, dtype=jnp.bfloat16) -> dict:
    """The canonical tensors of one model in ``dtype``: one program."""
    return draw(sz, words, dtype)


# ---- the program's side ----------------------------------------------

# ModelConfig field -> the config file's key
STATED = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
          "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads",
          "d_ff": "moe_intermediate_size", "dense_d_ff": "intermediate_size",
          "vocab_size": "vocab_size", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_head_dim",
          "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
          "n_experts": "routed_experts_published",
          "experts_held": "n_routed_experts",
          "expert_offset": "routed_experts_offset",
          "top_k": "num_experts_per_tok",
          "n_shared_experts": "n_shared_experts",
          "first_k_dense": "first_k_dense_replace",
          "norm_topk_prob": "norm_topk_prob",
          "routed_scaling": "routed_scaling_factor",
          "tie_embeddings": "tie_word_embeddings",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "dtype": "torch_dtype"}
YARN = {"yarn_factor": "factor",
        "yarn_original_max_pos": "original_max_position_embeddings",
        "yarn_beta_fast": "beta_fast", "yarn_beta_slow": "beta_slow",
        "yarn_mscale": "mscale", "yarn_mscale_all_dim": "mscale_all_dim"}
# published settings the program has one way of doing
FIXED = {"q_lora_rank": None, "scoring_func": "softmax",
         "topk_method": "greedy", "n_group": 1, "topk_group": 1,
         "moe_layer_freq": 1, "attention_bias": False, "hidden_act": "silu"}


def served_config(model: dict):
    """The program's ``ModelConfig`` for a config file, checked against
    every size and setting the file states."""
    from repro.configs import get_config
    cfg = get_config(model["arch_id"]).variant(**model.get("overrides", {}))
    want = [(f, getattr(cfg, f), model[k]) for f, k in STATED.items()]
    want += [(f, getattr(cfg, f), model["rope_scaling"][k])
             for f, k in YARN.items()]
    want += [(k, v, model[k]) for k, v in FIXED.items()]
    for f, got, stated in want:
        if got != stated:
            raise ValueError(f"{model['arch_id']}: the program's {f}={got!r} "
                             f"departs from the stated {stated!r}")
    if model["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"{model['arch_id']}: rope scaling is not YaRN")
    if not (cfg.is_mla and cfg.family == "moe"
            and cfg.decode_window() is None):
        raise ValueError(f"{model['arch_id']}: served config is not a "
                         f"full-attention latent expert decoder")
    return cfg


def program_params(cfg, sz: Sizes, words):
    """The seeded canonical weights, laid out as the program's params
    pytree (checked against ``init_params``'s shapes and dtypes)."""
    from repro.models import model as M
    tmpl = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    w = canonical(sz, words, jnp.dtype(cfg.dtype))
    vp = tmpl["embed"]["w"].shape[0]
    k = sz.dense_layers

    def rows(x):
        return {"w": jnp.pad(x, ((0, vp - x.shape[0]), (0, 0)))}

    def attn(sl):
        return {"ln1": {"scale": w["ln1"][sl]},
                "attn": {"wq": {"w": w["wq"][sl]},
                         "wkv_a": {"w": w["wkv_a"][sl]},
                         "kv_norm": {"scale": w["kv_norm"][sl]},
                         "wkv_b": {"w": w["wkv_b"][sl]},
                         "wo": {"w": w["wo"][sl]}},
                "ln2": {"scale": w["ln2"][sl]}}

    dense = dict(attn(slice(0, k)), mlp={
        "w_gate": {"w": w["d_gate"]}, "w_up": {"w": w["d_up"]},
        "w_down": {"w": w["d_down"]}})
    experts = dict(attn(slice(k, None)), moe={
        "router": {"w": w["router"]}, "w_gate": w["e_gate"],
        "w_up": w["e_up"], "w_down": w["e_down"],
        "shared": {"w_gate": {"w": w["s_gate"]}, "w_up": {"w": w["s_up"]},
                   "w_down": {"w": w["s_down"]}}})
    p = {"embed": rows(w["embed"]), "layers": experts,
         "final_norm": {"scale": w["final_norm"]}}
    if k:
        p["dense_layers"] = dense
    if "lm_head" in w:
        p["lm_head"] = rows(w["lm_head"])
    # the program may hold a tensor wider than published (the router in
    # float32): cast to its dtype, shapes must agree as they are
    p = jax.tree.map(lambda a, t: a.astype(t.dtype), p, tmpl)
    want = jax.tree.map(lambda s: (s.shape, s.dtype), tmpl)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), p)
    if want != got:
        raise ValueError(f"weights do not match the program's parameter "
                         f"layout for {cfg.arch_id}")
    del w
    return p


# ---- the plain reference ----------------------------------------------

def _round_channels(w, mode: str, axis: int):
    """Round ``w`` to int8 or float8_e4m3 with one scale per output
    channel (``axis`` is the reduced, input axis); float32 out."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {mode!r}")


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(sz: Sizes) -> np.ndarray:
    factor, orig, fast, slow, _, _ = sz.yarn
    dim, base = sz.rope, sz.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo, hi = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)),
                                                  dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo if hi > lo
                                                 else 0.001), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def _rope(x, pos, sz: Sizes):
    """x [T, ..., R] at positions pos [T]: rotate the two halves."""
    factor, _, _, _, ms, ms_all = sz.yarn
    scale = _mscale(factor, ms) / _mscale(factor, ms_all)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(_inv_freq(sz))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[-1],)
    c = (jnp.cos(ang) * scale).reshape(shape)
    s = (jnp.sin(ang) * scale).reshape(shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _attention(sz: Sizes, q, k, v):
    """Causal attention in blocks of queries. q, k [T, H, N+R]; v
    [T, H, V]."""
    T, H, dq = q.shape
    nb = T // Q_BLOCK
    scale = 1.0 / math.sqrt(dq) * _mscale(sz.yarn[0], sz.yarn[5]) ** 2
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,thd->hqt", qi, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, -1), v,
                          precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), q.reshape(nb, Q_BLOCK, H, dq)))
    return out.reshape(T, -1)


def experts_ref(sz: Sizes, wl: dict, x, mode: str = "f32"):
    """One expert layer of the reference on normed hidden states x [T, D]
    (float32), with one layer's tensors ``wl``: the shared expert plus
    each held expert's SwiGLU times its gate, zero where not picked."""
    def mat(m):
        return (_round_channels(m, mode, -2) if mode != "f32"
                else m.astype(jnp.float32))

    def swiglu(g, u, d):
        def dot(a, m):
            return jnp.dot(a, mat(m), precision=HI)
        return dot(jax.nn.silu(dot(x, g)) * dot(x, u), d)

    probs = jax.nn.softmax(
        jnp.dot(x, wl["router"].astype(jnp.float32), precision=HI), -1)
    gate, idx = jax.lax.top_k(probs, sz.top_k)
    if sz.norm_topk:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    gate = gate * sz.scaling
    y = swiglu(wl["s_gate"], wl["s_up"], wl["s_down"])
    for i in range(sz.held):
        g = jnp.sum(jnp.where(idx == sz.offset + i, gate, 0.0), -1)
        y = y + g[:, None] * swiglu(wl["e_gate"][i], wl["e_up"][i],
                                    wl["e_down"][i])
    return y


@partial(jax.jit, static_argnums=(0, 4))
def logits_at(sz: Sizes, w: dict, ids, want, mode: str = "f32"):
    """Logits [P, vocab] at positions ``want`` [P] of the sequence ``ids``
    [T] (T a multiple of ``Q_BLOCK``; padding after the last real token
    does not reach earlier positions). ``mode`` ``"f32"`` is the
    reference; ``"int8"``/``"fp8"`` round every projection and the head
    per output channel, the controls."""
    f32 = jnp.float32
    T = ids.shape[0]
    pos = jnp.arange(T)
    H, N, R, C = sz.heads, sz.nope, sz.rope, sz.kv_rank
    ctl = mode != "f32"

    def mat(m):
        return _round_channels(m, mode, -2) if ctl else m.astype(f32)

    def dot(x, m):
        return jnp.dot(x, mat(m), precision=HI)

    def swiglu(x, g, u, d):
        return dot(jax.nn.silu(dot(x, g)) * dot(x, u), d)

    def attn(h, wl):
        x = _rms(h, wl["ln1"].astype(f32), sz.norm_eps)
        q = dot(x, wl["wq"]).reshape(T, H, N + R)
        kv = dot(x, wl["wkv_a"])
        c_kv = _rms(kv[:, :C], wl["kv_norm"].astype(f32), sz.norm_eps)
        k_pe = _rope(kv[:, C:], pos, sz)
        q = jnp.concatenate([q[..., :N], _rope(q[..., N:], pos, sz)], -1)
        kvb = dot(c_kv, wl["wkv_b"]).reshape(T, H, N + sz.v_dim)
        k = jnp.concatenate(
            [kvb[..., :N], jnp.broadcast_to(k_pe[:, None], (T, H, R))], -1)
        h = h + dot(_attention(sz, q, k, kvb[..., N:]), wl["wo"])
        return h, _rms(h, wl["ln2"].astype(f32), sz.norm_eps)

    def dense_layer(h, wl):
        h, x = attn(h, wl)
        return h + swiglu(x, wl["d_gate"], wl["d_up"], wl["d_down"]), None

    def expert_layer(h, wl):
        h, x = attn(h, wl)
        return h + experts_ref(sz, wl, x, mode), None

    k = sz.dense_layers
    att = ("ln1", "ln2", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
    h = w["embed"][ids].astype(f32)
    if k:
        first = {n: w[n][:k] for n in att}
        first.update({n: w[n] for n in ("d_gate", "d_up", "d_down")})
        h, _ = jax.lax.scan(dense_layer, h, first)
    rest = {n: w[n][k:] for n in att}
    rest.update({n: w[n] for n in ("router", "s_gate", "s_up", "s_down")
                 + EXPERT_BANKS})
    h, _ = jax.lax.scan(expert_layer, h, rest)
    h = _rms(h[want], w["final_norm"].astype(f32), sz.norm_eps)
    head = w["embed"] if sz.tied else w["lm_head"]
    head = _round_channels(head, mode, -1) if ctl else head.astype(f32)
    return jnp.dot(h, head.T, precision=HI)


# ---- operations and bytes -----------------------------------------------

def attention_params(sz: Sizes) -> int:
    """Weights of one layer's attention."""
    H, D, C, R = sz.heads, sz.d_model, sz.kv_rank, sz.rope
    return (D * H * (sz.nope + R) + D * (C + R) + C
            + C * H * (sz.nope + sz.v_dim) + H * sz.v_dim * D)


def decode_flops(sz: Sizes, ctx_len: int) -> float:
    """Operations one decode row needs at context ``ctx_len``, the active
    work: every attention projection, with W_uk and W_uv absorbed (the
    query into the latent space and the latent output out of it), and the
    absorbed attention over ``ctx_len`` latent lines; layer 0's SwiGLU;
    in each expert layer the router, the shared expert and the expected
    routed experts held here (top_k x held / experts); the output head.
    Two operations per weight and per multiply-add."""
    H, D, C, R, N, V = (sz.heads, sz.d_model, sz.kv_rank, sz.rope, sz.nope,
                        sz.v_dim)
    proj = D * H * (N + R) + D * (C + R) + H * N * C + H * C * V + H * V * D
    attn = H * (C + R) * ctx_len + H * C * ctx_len
    per_expert = 3 * D * sz.expert_ff
    expert_layer = (D * sz.experts + sz.shared * per_expert
                    + sz.top_k * sz.held / sz.experts * per_expert)
    k = sz.dense_layers
    return float(2 * (sz.layers * (proj + attn) + k * 3 * D * sz.dense_ff
                      + (sz.layers - k) * expert_layer + sz.vocab * D))


def line_bytes(sz: Sizes) -> int:
    """Bytes of one token's latent line in one layer: c_kv and k_pe."""
    return (sz.kv_rank + sz.rope) * BF16


def mla_decode_bytes(sz: Sizes, lines: float) -> float:
    """HBM bytes one decode step's attention needs: the ``lines`` latent
    lines its rows attend (each row's live positions, summed over rows),
    in every layer, and every layer's attention weights."""
    return sz.layers * (lines * line_bytes(sz) + attention_params(sz) * BF16)


def moe_decode_bytes(sz: Sizes, experts_hit: float) -> float:
    """HBM bytes one decode step's expert layers need: ``experts_hit``
    held experts (summed over layers), plus every expert layer's shared
    expert and router."""
    per_expert = 3 * sz.d_model * sz.expert_ff * BF16
    per_layer = (sz.shared * per_expert
                 + sz.d_model * sz.experts * BF16)
    return experts_hit * per_expert + (sz.layers - sz.dense_layers) * per_layer


# ---- device time by scope -----------------------------------------------

_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scopes_of_hlo(text: str, scopes=("mla", "moe")) -> dict:
    """Instruction name -> the scope (one of ``scopes``) its op was traced
    under, from compiled HLO text: the instruction's own ``op_name``, or,
    for a fusion without one, the scope most of its fused instructions
    carry. A custom call whose ``op_name`` is its kernel's own name (the
    TPU's ``jax.lax.ragged_dot`` is a Mosaic call named
    ``ragged-dot-none``, which keeps no scope) takes the scope most of its
    users carry. Instructions under no such scope are left out."""
    def scope(op_name):
        parts = op_name.split("/")
        return next((s for s in scopes if s in parts), None)

    def most(found):
        found = [x for x in found if x]
        return max(set(found), key=found.count) if found else None

    comps, calls, own, users, kernels = {}, {}, {}, {}, []
    current = None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            current = comps.setdefault(m.group(1), [])
            continue
        m = _NAME.match(line)
        if not m:
            continue
        name = m.group(1)
        callee, op_name = _CALLS.search(line), _OP_NAME.search(line)
        s = scope(op_name.group(1)) if op_name else None
        if current is not None:
            current.append(s)
        own[name] = s
        if callee and not op_name:
            calls[name] = callee.group(1)
        if " custom-call(" in line and s is None:
            kernels.append(name)
        for operand in _OPERAND.findall(line[m.end():]):
            users.setdefault(operand, []).append(name)
    out = {}
    for name, s in own.items():
        if name in calls:
            s = most(comps.get(calls[name], []))
        if s is not None:
            out[name] = s
    for name in kernels:
        s = most(out.get(u) for u in users.get(name, ()))
        if s is not None:
            out[name] = s
    return out


@functools.lru_cache(maxsize=4)
def _decode_scopes(model_json: str, slots: int, max_len: int) -> dict:
    from repro.kernels import dispatch as kd
    from repro.models import model as M
    from repro.serving.engine import _jit_steps
    cfg = served_config(json.loads(model_json))
    dt = jnp.dtype(cfg.dtype)
    params = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=dt))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, slots, max_len,
                                                dtype=dt))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    decode_fn, _ = _jit_steps(cfg, max_len, kd.use_pallas())
    text = decode_fn.lower(params, sd((slots, 1), i32), sd((slots,), i32),
                           cache, key, sd((slots,), f32),
                           sd((slots,), i32)).compile().as_text()
    return scopes_of_hlo(text)


def decode_scopes(cell) -> dict:
    """Scope of each op of the cell's decode program, keyed as the trace
    reduction keys op time (``jit_decode_fn/<instruction>``). Compiles
    the program again (a hit in the persistent compile cache when the run
    compiled it), after the window."""
    d = cell.cell["deployment"]
    got = _decode_scopes(json.dumps(cell.model, sort_keys=True),
                         int(d["slots"]), int(d["max_len"]))
    return {f"jit_decode_fn/{k}": v for k, v in got.items()}


def decode_steps(r):
    """(decode rows' attended lines, summed, and the distinct decode
    steps) of the tokens that became visible inside the traced window:
    token ``j`` after a request's first was decoded attending its context
    and the ``j`` tokens before it."""
    max_len = int(r.cell.cell["deployment"]["max_len"])
    lo, hi = r.ctx.trace_host
    lines, steps = 0, set()
    for tr in r.recorder.tracked():
        ctx0 = min(len(tr.req.prompt_ids),
                   max_len - tr.req.max_new_tokens - 1)
        for j, t in enumerate(tr.times[1:], 1):
            if lo <= t <= hi:
                lines += ctx0 + j
                steps.add(t)
    return lines, len(steps)


def scope_roofline(r, scope: str, step_bytes) -> "float | None":
    """Share (%) of the HBM roofline that the ops under ``scope`` reach
    per execution of the decode program: ``step_bytes(r, lines, steps)``
    (the bytes one step needs) over peak bandwidth, over the scope's
    device time per execution. Nothing without a trace, a decode step or
    an op under the scope."""
    if r.reduced is None or r.ctx.trace_host is None:
        return None
    _, n = r.reduced.program_time("jit_decode_fn")
    lines, steps = decode_steps(r)
    if not (n and steps):
        return None
    scopes = decode_scopes(r.cell)
    t = sum(v for k, v in r.reduced.ops.items() if scopes.get(k) == scope)
    if t <= 0:
        return None
    need = step_bytes(r, lines, steps) / r.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (t / n)
