"""Mixture-of-Experts layer with capacity-based sorted dispatch.

Design (TPU-native, MaxText-style): tokens are routed top-k, sorted by
expert id, gathered into a dense [E, capacity, D] buffer, processed with
one batched einsum per projection (MXU-friendly), and scattered back with
gate weighting. Compiled FLOPs are O(E · capacity · D · F) ≈
O(tokens · top_k · cf · D · F) — the *active* compute, not n_experts×
dense compute, which keeps the roofline "useful FLOPs" ratio honest for
the 384-expert kimi-k2 config.

Expert parallelism: the leading E axis of the expert weights is sharded on
the "model" mesh axis when divisible (kimi: 384/16); otherwise the F axis
is sharded (mixtral: 8 experts < 16 shards ⇒ TP inside experts). GSPMD
turns the gather/scatter into all-to-all on the sharded axis.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


def init_moe(key, cfg, *, dtype=jnp.float32):
    """Router over all ``n_experts``; expert banks of the experts held."""
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    Eh = cfg.experts_held
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(D)
    p = {
        "router": L.dense_init(ks[0], D, E, dtype=jnp.float32),
        "w_gate": jax.random.normal(ks[1], (Eh, D, F), dtype) * s,
        "w_up": jax.random.normal(ks[2], (Eh, D, F), dtype) * s,
        "w_down": jax.random.normal(ks[3], (Eh, F, D), dtype) / math.sqrt(F),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.init_mlp(ks[4], cfg, dtype=dtype,
                                 d_ff=cfg.d_ff * cfg.n_shared_experts)
    return p


def _capacity(n_tokens: int, cfg) -> int:
    cap = int(math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, ((cap + 7) // 8) * 8)  # pad to VPU sublane multiple


def moe_forward(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [B,S,D] -> (y [B,S,D], aux_loss scalar). Dispatches to the
    shard_map expert-parallel path when cfg.moe_impl == "ep" and a mesh
    context is active (§Perf)."""
    if cfg.moe_impl == "ep":
        from repro.distributed.context import get_mesh
        mesh = get_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and cfg.n_experts % mesh.shape["model"] == 0:
            return moe_forward_ep(p, cfg, x, mesh)
    return _moe_forward_gather(p, cfg, x)


def _moe_forward_gather(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = L.dense(p["router"], xt.astype(jnp.float32))      # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # [T,K]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # load-balance aux loss (Switch-style): E * mean(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (T * K))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- sorted capacity dispatch ------------------------------------
    C = _capacity(T, cfg)
    flat_e = expert_idx.reshape(-1)                             # [T*K]
    flat_g = gate_vals.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), K)

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    # slot of each routed pair within its expert group
    pos = jnp.arange(T * K)
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(E), side="left")
    slot = pos - seg_start[e_sorted]
    keep = slot < C
    dst = jnp.where(keep, e_sorted * C + slot, E * C)           # overflow bin

    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[dst].set(xt[flat_tok[order]])
    buf = buf[:-1].reshape(E, C, D)

    # ---- expert compute (batched, MXU) --------------------------------
    h_g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    h_u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = jax.nn.silu(h_g) * h_u
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])            # [E,C,D]

    # ---- weighted scatter back -----------------------------------------
    y_flat = y_e.reshape(E * C, D)
    gathered = jnp.where(keep[:, None], y_flat[jnp.minimum(dst, E * C - 1)], 0.0)
    contrib = gathered * flat_g[order][:, None].astype(x.dtype)
    y = jnp.zeros((T, D), x.dtype).at[flat_tok[order]].add(contrib)

    if "shared" in p:
        y = y + L.mlp(p["shared"], cfg, xt)
    return y.reshape(B, S, D), aux


def moe_forward_ep(p, cfg, x, mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """§Perf: shard_map expert parallelism.

    Activations are batch-sharded over the data axes and replicated over
    "model"; expert banks are sharded on "model". Each model shard
    routes its (replicated) local tokens, keeps only assignments to ITS
    E/ms experts, runs them, and the partial outputs are combined with a
    single psum over "model" — the same collective shape as a
    row-parallel linear. This replaces the GSPMD-global argsort+scatter
    of the gather dispatch, whose all-to-all/all-gather volume made the
    MoE train shapes collective-bound (see EXPERIMENTS.md §Perf #1).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    # check_vma=False: with the varying-manual-axes checker enabled, the
    # TRANSPOSE of this body (sort+scatter over an input replicated on
    # "model", sharded on the data axes) produces silently wrong router
    # gradients on mixed meshes (verified against finite differences —
    # tests/test_moe_ep.py). With the checker off, gradients match the
    # dense oracle to 5e-7.
    _smap = lambda f, m, ins, outs: shard_map(
        f, mesh=m, in_specs=ins, out_specs=outs, check_vma=False)

    B, Sq, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    ms = mesh.shape["model"]
    E_loc = E // ms
    import math as _m
    dsz = _m.prod(mesh.shape[a] for a in dp)
    T_loc = (B // dsz) * Sq if B % dsz == 0 else B * Sq
    # local capacity: expected local tokens routed to each local expert
    C = max(8, int(_m.ceil(T_loc * K * cfg.capacity_factor / E / 8)) * 8)

    def local_fn(x_loc, router_w, wg, wu, wd):
        # x_loc [b_loc, S, D] (replicated over model); wg [E_loc, D, F]
        b_loc = x_loc.shape[0]
        xt = x_loc.reshape(-1, D)
        T = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router_w
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        # aux loss: identical on every model shard (x replicated there) —
        # pmean keeps the value but splits the cotangent 1/ms per shard so
        # the router gradient is not overcounted ms times
        me = jnp.mean(probs, axis=0)
        ce_ = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
            1.0 / (T * K))
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce_)
        aux = jax.lax.pmean(aux, "model")

        my_lo = jax.lax.axis_index("model") * E_loc
        flat_e = expert_idx.reshape(-1)
        flat_g = gate_vals.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T), K)
        local_e = jnp.where((flat_e >= my_lo) & (flat_e < my_lo + E_loc),
                            flat_e - my_lo, E_loc)          # E_loc = not mine
        order = jnp.argsort(local_e, stable=True)
        e_sorted = local_e[order]
        pos = jnp.arange(T * K)
        seg_start = jnp.searchsorted(e_sorted, jnp.arange(E_loc + 1),
                                     side="left")
        slot = pos - seg_start[jnp.minimum(e_sorted, E_loc)]
        keep = (e_sorted < E_loc) & (slot < C)
        dst = jnp.where(keep, e_sorted * C + slot, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, D), x_loc.dtype)
        buf = buf.at[dst].set(jnp.where(keep[:, None],
                                        xt[flat_tok[order]], 0))
        buf = buf[:-1].reshape(E_loc, C, D)
        h_g = jnp.einsum("ecd,edf->ecf", buf, wg)
        h_u = jnp.einsum("ecd,edf->ecf", buf, wu)
        y_e = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h_g) * h_u, wd)
        y_flat = y_e.reshape(E_loc * C, D)
        gathered = jnp.where(keep[:, None],
                             y_flat[jnp.minimum(dst, E_loc * C - 1)], 0)
        contrib = gathered * flat_g[order][:, None].astype(x_loc.dtype)
        y = jnp.zeros((T, D), x_loc.dtype).at[flat_tok[order]].add(contrib)
        y = jax.lax.psum(y, "model")
        # scalar aux as a vector so it can ride the dp sharding
        aux_vec = jnp.full((b_loc,), aux / B, jnp.float32)
        return y.reshape(b_loc, Sq, D), aux_vec

    batch_spec = P(dp if B % dsz == 0 else None, None, None)
    y, aux_vec = _smap(
        local_fn, mesh,
        (batch_spec, P(None, None), P("model", None, None),
         P("model", None, None), P("model", None, None)),
        (batch_spec, P(dp if B % dsz == 0 else None)),
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    aux = jnp.sum(aux_vec)
    if "shared" in p:
        from repro.models import layers as L
        y = y + L.mlp(p["shared"], cfg, x)
    return y, aux


def moe_dropless(p, cfg, x, rows=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless expert layer over the experts this chip holds.

    ``g = softmax(W_g x)`` in float32 over all ``n_experts``; greedy
    top-k (renormalised only under ``norm_topk_prob``, times
    ``routed_scaling``); ``y = S(x) + sum over picked-and-held i of
    g_i E_i(x)``, where ``S`` is the shared expert. Every routed (token,
    held expert) pair is computed: the pairs are sorted by expert and run
    through one grouped matmul per projection (``jax.lax.ragged_dot``),
    so the work follows the routed pairs, no capacity applies, and a
    row's result does not depend on the other rows. Pairs routed to
    experts held elsewhere sort last, past every group, and add nothing.

    x [B,S,D] -> (y [B,S,D], picks [held] int32: the pairs each held
    expert computed for the rows that ``rows`` [B] marks, every row where
    it is None)."""
    B, S, D = x.shape
    K, n = cfg.top_k, cfg.experts_held
    xt = x.reshape(-1, D)
    logits = xt.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)   # [T,K]
    if cfg.norm_topk_prob:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate * cfg.routed_scaling
    local = idx - cfg.expert_offset
    mine = (local >= 0) & (local < n)
    e = jnp.where(mine, local, n).reshape(-1)                      # [T*K]
    order = jnp.argsort(e, stable=True)
    pair = e[:, None] == jnp.arange(n)[None, :]                     # [T*K,n]
    sizes = jnp.sum(pair, axis=0, dtype=jnp.int32)                  # [n]
    picks = sizes if rows is None else jnp.sum(
        pair & jnp.repeat(rows, S * K)[:, None], axis=0, dtype=jnp.int32)
    tok = order // K
    xs = xt[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) * \
        jax.lax.ragged_dot(xs, p["w_up"], sizes)
    ys = jax.lax.ragged_dot(h, p["w_down"], sizes)                  # [T*K,D]
    held = (e < n)[order]
    w = gate.reshape(-1)[order]
    contrib = jnp.where(held[:, None], ys.astype(jnp.float32) * w[:, None],
                        0.0)
    y = jnp.zeros((xt.shape[0], D), jnp.float32).at[tok].add(contrib)
    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + L.mlp(p["shared"], cfg, xt)
    return y.reshape(B, S, D), picks


def moe_forward_dense(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference oracle: run every expert on every token, mask by gates."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, D)
    logits = L.dense(p["router"], xt.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
    dense_gates = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], expert_idx].set(gate_vals)  # [T,E]
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"])) * jnp.einsum(
        "td,edf->tef", xt, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])
    y = jnp.einsum("ted,te->td", y_all, dense_gates.astype(x.dtype))
    if "shared" in p:
        y = y + L.mlp(p["shared"], cfg, xt)
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (xt.shape[0] * K))
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux
