"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-3


# ---- flash attention ------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Sk, H, KV, hd, causal, window)
    (2, 64, 64, 4, 2, 32, True, None),
    (1, 128, 128, 8, 8, 64, True, None),
    (2, 96, 96, 4, 1, 32, True, 32),      # SWA + max GQA
    (1, 37, 80, 2, 2, 16, False, None),   # ragged cross-attn
    (1, 200, 200, 2, 1, 128, True, 64),   # hd=128 MXU tile
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Sq, Sk, H, KV, hd, causal, window = case
    # crc32, not hash(): tuples holding None hash process-randomized < 3.12
    ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(repr(case).encode())), 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Sk, KV, hd), dtype)
    v = _rand(ks[2], (B, Sk, KV, hd), dtype)
    off = Sk - Sq if causal else 0
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=off, bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.integers(8, 70), st.integers(1, 4),
       st.sampled_from([16, 32]), st.booleans(), st.integers(0, 10_000))
def test_flash_attention_property(B, S, KV, hd, causal, seed):
    """Property: kernel == oracle for arbitrary shapes incl. non-multiples."""
    H = KV * 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rand(ks[0], (B, S, H, hd), jnp.float32)
    k = _rand(ks[1], (B, S, KV, hd), jnp.float32)
    v = _rand(ks[2], (B, S, KV, hd), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, bq=16, bk=16)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


def test_flash_attention_softmax_invariance():
    """Scaling all scores by adding a constant to q·k via key shift must not
    change softmax output materially (online-softmax stability)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (1, 32, 2, 16), jnp.float32)
    k = _rand(ks[1], (1, 32, 2, 16), jnp.float32)
    v = _rand(ks[2], (1, 32, 2, 16), jnp.float32)
    o1 = ops.flash_attention(q, k, v, bq=16, bk=16)
    o2 = ops.flash_attention(q * 4.0, k, v, bq=16, bk=16)  # sharp softmax
    assert np.isfinite(np.asarray(o2)).all()
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


# ---- decode attention -----------------------------------------------------

DECODE_CASES = [
    (2, 4, 2, 32, 96),
    (3, 8, 8, 64, 130),
    (1, 2, 1, 128, 512),
    (4, 12, 2, 128, 64),
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(case, dtype):
    B, H, KV, hd, M = case
    ks = jax.random.split(jax.random.PRNGKey(zlib.crc32(repr(case).encode())), 3)
    q = _rand(ks[0], (B, 1, H, hd), dtype)
    k = _rand(ks[1], (B, M, KV, hd), dtype)
    v = _rand(ks[2], (B, M, KV, hd), dtype)
    kv_len = jnp.asarray([max(1, M - 7 * i) for i in range(B)], jnp.int32)
    out = ops.decode_attention(q, k, v, kv_len=kv_len, bk=32)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3), st.integers(4, 80), st.integers(1, 10_000))
def test_decode_attention_property(B, M, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = _rand(ks[0], (B, 1, 4, 32), jnp.float32)
    k = _rand(ks[1], (B, M, 2, 32), jnp.float32)
    v = _rand(ks[2], (B, M, 2, 32), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, M + 1)
    out = ops.decode_attention(q, k, v, kv_len=kv_len, bk=16)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


# ---- rmsnorm --------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 64), (100, 128), (3, 7, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = _rand(ks[0], shape, dtype)
    s = _rand(ks[1], shape[-1:], jnp.float32)
    out = ops.rmsnorm(x, s)
    want = ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_model_path_with_pallas_enabled():
    """End-to-end: enabling the Pallas dispatch reproduces the jnp model."""
    from repro.kernels.dispatch import pallas_enabled
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("internlm2-1.8b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = {"tokens": jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % cfg.vocab_size}
    ref_logits, _ = M.forward(params, cfg, batch)
    with pallas_enabled(True):
        pl_logits, _ = M.forward(params, cfg, batch)
    np.testing.assert_allclose(np.asarray(pl_logits), np.asarray(ref_logits),
                               atol=5e-3, rtol=5e-3)


# ---- interpret-mode dispatch ----------------------------------------------

def test_interpret_mode_follows_backend_only(monkeypatch):
    """No environment variable can force interpret mode either way: it
    is the backend's call (CPU interprets, a TPU compiles)."""
    from repro.kernels import dispatch
    for value in ("0", "1"):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
        assert dispatch.interpret_mode() == (jax.default_backend() != "tpu")
    assert dispatch.resolve_interpret(None) == dispatch.interpret_mode()
    assert dispatch.resolve_interpret(False) is False
    assert dispatch.resolve_interpret(True) is True


KERNEL_ENTRY_POINTS = [
    ("ragged_prefill_attention", "ragged_prefill_attention_bhsd"),
    ("batched_decode_attention", "batched_decode_attention_bhmd"),
    ("decode_attention", "decode_attention_bhmd"),
    ("flash_attention", "flash_attention_bhsd"),
    ("rmsnorm", "rmsnorm_2d"),
    ("chunked_gla", "chunked_gla_bhtd"),
]


@pytest.mark.parametrize("module,name", KERNEL_ENTRY_POINTS)
def test_kernel_entry_point_interpret_defaults_to_backend(module, name):
    import importlib
    import inspect
    fn = getattr(importlib.import_module(f"repro.kernels.{module}"), name)
    assert inspect.signature(fn).parameters["interpret"].default is None


def test_omitted_interpret_resolves_to_interpret_on_cpu():
    """A kernel called without ``interpret`` on the CPU backend runs in
    interpret mode (it cannot compile for the CPU) and matches its
    oracle."""
    from repro.kernels.rmsnorm import rmsnorm_2d
    assert jax.default_backend() == "cpu"
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = _rand(ks[0], (16, 128), jnp.float32)
    s = _rand(ks[1], (128,), jnp.float32)
    np.testing.assert_allclose(np.asarray(rmsnorm_2d(x, s)),
                               np.asarray(ref.rmsnorm_ref(x, s)),
                               atol=2e-3, rtol=2e-3)
