"""§Perf optimization knobs: numerical parity with the baselines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M


def test_blocked_attention_model_parity():
    cfg0 = get_config("internlm2-1.8b").reduced()
    cfg1 = cfg0.variant(attention_block_q=8)
    p = M.init_params(cfg0, jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = {"tokens": jnp.arange(64, dtype=jnp.int32).reshape(2, 32)
             % cfg0.vocab_size}
    l0, _ = M.forward(p, cfg0, batch)
    l1, _ = M.forward(p, cfg1, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               atol=1e-4, rtol=1e-4)


def test_blocked_attention_swa_parity():
    cfg0 = get_config("mixtral-8x7b").reduced().variant(
        sliding_window=8, capacity_factor=8.0)
    cfg1 = cfg0.variant(attention_block_q=8)
    p = M.init_params(cfg0, jax.random.PRNGKey(1), dtype=jnp.float32)
    batch = {"tokens": jnp.arange(48, dtype=jnp.int32).reshape(2, 24)
             % cfg0.vocab_size}
    l0, _ = M.forward(p, cfg0, batch)
    l1, _ = M.forward(p, cfg1, batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               atol=1e-4, rtol=1e-4)


def test_expand_kv_decode_parity():
    cfg0 = get_config("mistral-large-123b").reduced().variant(n_kv_heads=2)
    cfg1 = cfg0.variant(kv_cache_expand_heads=4)
    key = jax.random.PRNGKey(0)
    p = M.init_params(cfg0, key, dtype=jnp.float32)
    B, S = 2, 12
    toks = jax.random.randint(key, (B, S + 1), 0, cfg0.vocab_size)
    out = {}
    for name, cfg in (("base", cfg0), ("expand", cfg1)):
        cache = M.init_cache(cfg, B, S + 8, dtype=jnp.float32)
        _, cache = M.serve_prefill(p, cfg, {"tokens": toks[:, :S]}, cache)
        lg, _ = M.serve_decode(p, cfg, toks[:, S:S + 1],
                               jnp.full((B,), S, jnp.int32), cache)
        out[name] = lg
    np.testing.assert_allclose(np.asarray(out["expand"]),
                               np.asarray(out["base"]), atol=1e-5)


# Decode writes each token in place into the layer-stacked cache and
# attends the stored KV heads grouped. Every case runs serve_decode for
# several steps and holds it to the full-sequence forward.
DECODE_CASES = {
    # 4 query heads over 2 stored KV heads
    "grouped-heads": lambda: get_config("qwen2-1.5b").reduced().variant(
        long_context_window=None),
    # KV heads expanded in the cache to the query heads: groups of 1
    "expanded-heads": lambda: get_config("mistral-large-123b").reduced()
    .variant(n_kv_heads=2, kv_cache_expand_heads=4, long_context_window=None),
    # an 8-line rolling buffer stepped past its end, so pos % M wraps
    "rolling-window": lambda: get_config("mistral-large-123b").reduced()
    .variant(n_kv_heads=2, sliding_window=8, long_context_window=None),
    "moe": lambda: get_config("mixtral-8x7b").reduced().variant(
        n_kv_heads=2, sliding_window=None, capacity_factor=8.0),
    # the encoder-decoder's self-attention reads the same grouped path
    "encdec": lambda: get_config("whisper-medium").reduced().variant(
        n_kv_heads=2),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decode_in_place_parity(case):
    cfg = DECODE_CASES[case]()
    window = cfg.decode_window()
    B, S0, steps = 2, (6 if window else 10), 5
    M_ = window or S0 + steps + 3
    key = jax.random.PRNGKey(3)
    p = M.init_params(cfg, key, dtype=jnp.float32)
    toks = jax.random.randint(key, (B, S0 + steps), 0, cfg.vocab_size)
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = 0.1 * jnp.ones((B, cfg.encoder_seq, cfg.d_model))

    def prefill(n):
        cache = M.init_cache(cfg, B, M_, dtype=jnp.float32)
        return M.serve_prefill(p, cfg, dict(extra, tokens=toks[:, :n]),
                               cache)[1]

    full, _ = M.forward(p, cfg, dict(extra, tokens=toks))
    cache = prefill(S0)
    rows = np.arange(B)
    for pos in range(S0, S0 + steps):
        lg, new = M.serve_decode(p, cfg, toks[:, pos:pos + 1],
                                 jnp.full((B,), pos, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, pos]), atol=1e-3)
        assert set(new) == set(cache)
        for name in ("xk", "xv"):
            if name in cache:
                np.testing.assert_array_equal(np.asarray(new[name]),
                                              np.asarray(cache[name]))
        slot = pos % M_
        assert slot == pos or window
        want = prefill(pos + 1)
        for name in ("k", "v"):
            old_c, new_c = np.array(cache[name]), np.array(new[name])
            # the token lands at its line in every layer, as a prefill
            # through it would have written it
            np.testing.assert_allclose(new_c[:, rows, slot],
                                       np.asarray(want[name])[:, rows, slot],
                                       atol=1e-4)
            # and every other line is left exactly as it was
            old_c[:, rows, slot] = new_c[:, rows, slot] = 0
            np.testing.assert_array_equal(new_c, old_c)
        cache = new
    assert window is None or S0 + steps > M_


def test_bf16_optimizer_moments():
    from repro.training.optimizer import (AdamWConfig, AdamWState,
                                          adamw_update)
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt = AdamWState(step=jnp.zeros((), jnp.int32),
                     mu={"w": jnp.zeros(2, jnp.bfloat16)},
                     nu={"w": jnp.zeros(2, jnp.bfloat16)})
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, schedule="constant")
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, opt, _ = adamw_update(cfg, grads, opt, params)
    assert opt.mu["w"].dtype == jnp.bfloat16       # dtype preserved
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.1


def test_knapsack_policy_runs():
    from repro.core.hybridflow import Pipeline
    from repro.core.profiler import train_default_router
    router, _ = train_default_router(n_queries=60, epochs=30)
    pipe = Pipeline()
    from repro.data.tasks import gen_benchmark
    qs = gen_benchmark("gpqa", 30)
    m = pipe.knapsack(qs, router, budget=0.5)
    assert 0.0 < m.offload_rate < 1.0
    assert m.accuracy > 0.15
