"""Latent attention (MLA) and the dropless expert layer of the
deepseek-v2-lite stack, at a reduced size on the CPU in float32.

Tolerances: where two float32 computations of the same sums differ only
in their order, they agree to ~1e-6 of values of order 1; 2e-5 leaves
room for that and is ~500x under what bfloat16 in place of float32 gives
(a relative rounding of 4e-3 on every product).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.models import kvcache as KV
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import model as M
from repro.models import moe as MOE

TOL = 2e-5


def _cfg(**kw):
    # 4 routed experts, 2 held from expert 1, 2 a token
    return get_config("deepseek-v2-lite").reduced().variant(
        n_experts_held=2, expert_offset=1, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)


def test_yarn_frequencies_and_scale_are_deepseek_v2_lites():
    """The published YaRN setting (factor 40 over 4096 positions, beta
    32 / 1, 64 rope dims, theta 1e4): fast dimensions keep theta^(-2i/64),
    slow ones are divided by 40, with a linear ramp between dimensions
    10 and 23 of the 32; the softmax scale is 192^-0.5 (0.1 * 0.707 *
    ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite")
    inv = L.rope_inv_freq(cfg, 64)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(10, 23) - 10) / 13
    np.testing.assert_allclose(inv[10:23], plain[10:23] * (1 - ramp)
                               + plain[10:23] / 40 * ramp, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert MLA.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


def test_absorbed_decode_is_non_absorbed_attention(model):
    """One query against stored latent lines: W_uk and W_uv absorbed
    (decode) against the lines decompressed to per-head K and V
    (prefill's form), per row at its own number of valid lines."""
    cfg, params = model
    a = jax.tree.map(lambda x: x[0], params["layers"]["attn"])
    B, Mx = 3, 24
    H, N, R, C = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.kv_lora_rank)
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q_nope = jax.random.normal(ks[0], (B, 1, H, N))
    q_pe = jax.random.normal(ks[1], (B, 1, H, R))
    ckv = jax.random.normal(ks[2], (B, Mx, C))
    kpe = jax.random.normal(ks[3], (B, Mx, R))
    kv_len = jnp.asarray([5, 24, 13], jnp.int32)
    got = MLA.attend_absorbed(a, cfg, q_nope, q_pe, ckv, kpe, kv_len)
    want = MLA.attend_lines(a, cfg, q_nope, q_pe, ckv, kpe, kv_len - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=TOL * float(jnp.max(jnp.abs(want))))


def _prefill(params, cfg, prompts, slot_idx, slots, max_len, chunk=8):
    """Chunked prefill of ``prompts`` into ``slot_idx`` of one pool, all
    rows together; returns (last logits per row, cache)."""
    cache = M.init_cache(cfg, slots, max_len, dtype=jnp.float32)
    G = len(prompts)
    off = np.zeros(G, np.int32)
    last = [None] * G
    while any(off[i] < len(p) for i, p in enumerate(prompts)):
        rows = [i for i, p in enumerate(prompts) if off[i] < len(p)]
        toks = np.zeros((len(rows), chunk), np.int32)
        take = np.zeros(len(rows), np.int32)
        for j, i in enumerate(rows):
            part = prompts[i][off[i]:off[i] + chunk]
            toks[j, :len(part)] = part
            take[j] = len(part)
        lg, cache = M.serve_prefill_chunk(
            params, cfg, jnp.asarray(toks), cache,
            jnp.asarray([slot_idx[i] for i in rows], jnp.int32),
            jnp.asarray(off[rows]), jnp.asarray(take), kv_width=max_len)
        for j, i in enumerate(rows):
            off[i] += take[j]
            if off[i] == len(prompts[i]):
                last[i] = np.asarray(lg[j, 0])
    return last, cache


def test_a_row_is_the_same_alone_and_batched(model):
    """Batch independence, the reason the stack may take batched chunked
    prefill: a row's logits alone equal its logits beside three other
    rows, in chunked prefill and in batched decode, since routing is
    dropless."""
    cfg, params = model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(16, cfg.vocab_size, n).tolist()
               for n in (19, 7, 30, 12)]
    slots, max_len = 4, 48
    alone, c1 = _prefill(params, cfg, prompts[:1], [2], slots, max_len)
    batch, c4 = _prefill(params, cfg, prompts, [2, 0, 1, 3], slots, max_len)
    np.testing.assert_allclose(batch[0], alone[0], rtol=0, atol=TOL)
    tok = np.zeros((slots, 1), np.int32)
    tok[:, 0] = [5, 6, 7, 8]
    pos = np.full(slots, max_len - 1, np.int32)
    pos[2] = len(prompts[0])
    lg1, _ = M.serve_decode(params, cfg, jnp.asarray(tok), jnp.asarray(pos),
                            c1)
    pos[[0, 1, 3]] = [len(prompts[1]), len(prompts[2]), len(prompts[3])]
    lg4, _ = M.serve_decode(params, cfg, jnp.asarray(tok), jnp.asarray(pos),
                            c4)
    np.testing.assert_allclose(np.asarray(lg4[2]), np.asarray(lg1[2]),
                               rtol=0, atol=TOL)


def test_decode_load_counts_the_live_rows_only(model):
    """The decode step's expert load counts the rows that serve a request:
    a live row's load is the same whatever the parked row beside it holds,
    and the loads of single live rows add up to the load of both."""
    cfg, params = model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(16, cfg.vocab_size, n).tolist() for n in (11, 23)]
    _, c1 = _prefill(params, cfg, prompts[:1], [0], 2, 32)
    _, c2 = _prefill(params, cfg, prompts, [0, 1], 2, 32)
    tok = jnp.asarray([[5], [9]], jnp.int32)
    pos = jnp.asarray([len(p) for p in prompts], jnp.int32)

    def load(cache, live):
        return np.asarray(M.serve_decode_counted(
            params, cfg, tok, pos, cache, jnp.asarray(live, jnp.int32))[2])

    first, second, both = load(c2, [1, 0]), load(c2, [0, 1]), load(c2, [1, 1])
    np.testing.assert_array_equal(load(c1, [1, 0]), first)
    np.testing.assert_array_equal(both[:-1], first[:-1] + second[:-1])
    assert first[:-1].sum() <= cfg.top_k * (cfg.n_layers - cfg.first_k_dense)
    assert max(first[-1], second[-1]) <= both[-1] <= first[-1] + second[-1]


def test_dropless_layer_computes_every_routed_pair(model):
    """Every token routed to the same expert (the case a capacity drops):
    each held pair is computed, the picks count them, and the result is a
    direct per-token sum over the held experts plus the shared one."""
    cfg, params = model
    p = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    p["router"]["w"] = p["router"]["w"].at[:, 1].add(0.5)    # all pick 1
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                      (3, 8, cfg.d_model))
    y, picks = MOE.moe_dropless(p, cfg, x)
    xt = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xt @ p["router"]["w"], -1)
    gate, idx = jax.lax.top_k(probs, cfg.top_k)
    want = L.mlp(p["shared"], cfg, xt)
    for i in range(cfg.experts_held):
        e = cfg.expert_offset + i
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        h = jax.nn.silu(xt @ p["w_gate"][i]) * (xt @ p["w_up"][i])
        want = want + g[:, None] * (h @ p["w_down"][i])
        assert int(picks[i]) == int(jnp.sum(idx == e))
    assert int(picks[0]) == xt.shape[0]            # expert 1: every token
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(want), rtol=0, atol=TOL)


def test_latent_pool_decode_write_and_prefix_copy():
    """The in-place decode write lands on (layer, row, position) of both
    latent arrays and nowhere else (k_pe, stored lines-minor, through its
    lines-major view); the prefix copy moves a slot's first lines across
    every layer into another slot."""
    cfg = _cfg()
    cache = KV.init_latent_cache(cfg, 3, 16, jnp.float32)
    assert cache["kpe"].shape == (cfg.n_layers, 3, cfg.qk_rope_head_dim, 16)
    ckv = jax.random.normal(jax.random.PRNGKey(0), cache["ckv"].shape)
    kpe = jax.random.normal(jax.random.PRNGKey(1), cache["kpe"].shape)
    c_new = jnp.ones((3, 1, cfg.kv_lora_rank))
    r_new = -jnp.ones((3, 1, cfg.qk_rope_head_dim))
    pos = jnp.asarray([4, 0, 9], jnp.int32)
    a, b = KV.write_decode(ckv, KV.lines_major(kpe), c_new, r_new, (1, pos),
                           None)
    for x, old, v in ((a, ckv, 1.0), (b, KV.lines_major(kpe), -1.0)):
        x, old = np.asarray(x), np.asarray(old).copy()
        for row, line in enumerate([4, 0, 9]):
            assert (x[1, row, line] == v).all()
            old[1, row, line] = v
        np.testing.assert_array_equal(x, old)
    from repro.serving.engine import _jit_copy
    want = {"ckv": np.asarray(ckv).copy(), "kpe": np.asarray(kpe).copy()}
    got = _jit_copy(8)({"ckv": ckv, "kpe": kpe}, jnp.asarray([2], jnp.int32),
                       jnp.asarray([0], jnp.int32),
                       jnp.asarray([5], jnp.int32))
    want["ckv"][:, 0, :5] = want["ckv"][:, 2, :5]
    want["kpe"][:, 0, :, :5] = want["kpe"][:, 2, :, :5]
    for name, w in want.items():
        np.testing.assert_array_equal(np.asarray(got[name]), w)


def test_engine_serves_the_stack_batched_with_prefix_reuse(model):
    """Through the engine: batched chunked prefill and batched decode,
    greedy tokens the same with prefix reuse (a latent-pool copy) and
    without, and the decode steps' expert load in ``stats``."""
    from repro.serving.engine import ServingEngine
    cfg, params = model
    head = list(range(20, 52))
    prompts = [head + [60 + i] * (3 + i) for i in range(4)]

    def run(**kw):
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                            prefill_chunk=16, **kw)
        # the first prompt's lines are in slot 1 when the others come, and
        # the next one leases slot 0: it copies the shared head
        reqs = [eng.submit([90] * 9, max_new_tokens=5),
                eng.submit(prompts[0], max_new_tokens=5)]
        eng.run_until_done()
        reqs += [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
        eng.run_until_done()
        return [r.output_ids for r in reqs], eng

    ref, plain = run(prefix_reuse=False)
    out, eng = run()
    assert out == ref
    assert eng.batched_prefill and eng.stats["prefill_batch_max"] == 2
    assert eng.stats["prefix_copies"] > 0
    picks = [eng.stats[f"expert_picks_{i}"] for i in range(2)]
    # 2 slots x 2 picks x 1 expert layer per decode step, at most
    assert 0 < sum(picks) <= eng.stats["steps"] * 2 * cfg.top_k
    assert 0 < eng.stats["experts_hit"] <= eng.stats["steps"] * 2
    assert "expert_picks_0" not in ServingEngine(
        *_dense(), batch_slots=1, max_len=32).stats


def test_engine_counts_the_latent_lines_its_decode_reads(model,
                                                        monkeypatch):
    """``latent_lines_read`` sums, over decode steps, each row's lines up
    to the position it writes, rounded up to whole kernel blocks (8 lines
    here), for the rows that serve a request only: two of four slots
    stay parked throughout and count 0. ``latent_lines_stored`` is slots
    x lines per step. A dense engine has neither."""
    from repro.serving.engine import ServingEngine
    monkeypatch.setattr(kops, "latent_block_lines", lambda m: min(8, m))
    cfg, params = model
    eng = ServingEngine(cfg, params, batch_slots=4, max_len=64,
                        prefill_chunk=16)
    reqs = [eng.submit([30 + i] * (5 + 7 * i), max_new_tokens=4 + 6 * i)
            for i in range(2)]
    eng.run_until_done()
    want = 0
    for r in reqs:
        # decode writes the line of every output token but the last, at
        # positions len(prompt) .. len(prompt) + outputs - 2
        for pos in range(len(r.prompt_ids), len(r.prompt_ids)
                         + len(r.output_ids) - 1):
            want += -(-(pos + 1) // 8) * 8
    assert eng.stats["latent_lines_read"] == want > 0
    assert eng.stats["latent_lines_stored"] == eng.stats["steps"] * 4 * 64
    assert "latent_lines_read" not in ServingEngine(
        *_dense(), batch_slots=1, max_len=32).stats


def _dense():
    cfg = get_config("qwen2-1.5b").reduced()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_capacity_routed_experts_keep_the_legacy_prefill():
    """mixtral and kimi route by capacity, which couples rows: the engine
    keeps them on the batch-1 prefill; deepseek-v2-lite is admitted by
    what it is (latent attention, dropless, full attention)."""
    for arch in ("mixtral-8x7b", "kimi-k2-1t-a32b"):
        assert not M.chunked_prefill_ok(get_config(arch))
    assert M.chunked_prefill_ok(get_config("deepseek-v2-lite"))
    assert M.chunked_prefill_ok(get_config("qwen2-1.5b"))
