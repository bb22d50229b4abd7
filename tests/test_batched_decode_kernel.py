"""Batched decode-attention Pallas kernel vs the jnp reference, plus the
decode hot path end-to-end through the live ServingEngine (token
identity with the Pallas dispatch toggled, greedy sampling under
``jax_debug_nans``, and the bounded-retrace contract of the jitted
step pair)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.batched_decode_attention import (
    batched_decode_attention_bhmd)
from repro.kernels.decode_attention import decode_attention_bhmd
from repro.kernels.dispatch import pallas_enabled


def _inputs(B, M, H, KV, hd, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, M, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, M, KV, hd), jnp.float32).astype(dtype)
    kv_len = jax.random.randint(ks[3], (B,), 1, M + 1).astype(jnp.int32)
    return q, k, v, kv_len


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-3


# ---- kernel vs reference --------------------------------------------------

BATCHED_DECODE_CASES = [
    # (B, M, H, KV, hd, window)
    (2, 64, 4, 2, 32, None),
    (3, 130, 8, 8, 64, None),       # MHA, non-block-multiple cache
    (1, 512, 2, 1, 128, None),      # KV=1, hd=128 MXU tile
    (4, 96, 12, 2, 64, None),       # GQA group of 6
    (2, 64, 4, 2, 32, 16),          # sliding window over a full cache
    (3, 100, 6, 1, 32, 48),         # window + KV=1, ragged tail block
]


@pytest.mark.parametrize("case", BATCHED_DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_decode_sweep(case, dtype):
    B, M, H, KV, hd, window = case
    seed = zlib.crc32(repr(case).encode())
    q, k, v, kv_len = _inputs(B, M, H, KV, hd, seed=seed, dtype=dtype)
    out = ops.decode_attention(q, k, v, kv_len=kv_len, window=window, bk=32)
    want = ref.decode_attention_ref(q, k, v, kv_len, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("case", [c[:5] for c in BATCHED_DECODE_CASES
                                  if c[5] is None])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_xla_decode_matches_ref(case, dtype):
    """The XLA decode read (q grouped over the stored KV heads, no head
    repeat) against the reference over repeated heads; a row with an
    empty cache comes back as zeros."""
    from repro.models import layers as L

    B, M, H, KV, hd = case
    seed = zlib.crc32(repr(case).encode())
    q, k, v, kv_len = _inputs(B, M, H, KV, hd, seed=seed, dtype=dtype)
    if B > 1:
        kv_len = kv_len.at[0].set(0)
    out = L.decode_attention(q, k, v, kv_len=kv_len)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    assert out.dtype == want.dtype
    assert B == 1 or (np.asarray(out[0]) == 0).all()
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_batched_decode_kv_len_zero_rows_are_zero():
    """Slots with nothing in their cache skip every KV block and emit
    exact zeros (the safe-denominator finish), while live rows in the
    same launch stay correct."""
    B, M, H, KV, hd = 4, 64, 4, 2, 32
    q, k, v, kv_len = _inputs(B, M, H, KV, hd, seed=7)
    kv_len = kv_len.at[0].set(0).at[2].set(0)
    out = np.asarray(ops.decode_attention(q, k, v, kv_len=kv_len, bk=16))
    assert (out[0] == 0).all()
    assert (out[2] == 0).all()
    live = np.asarray([1, 3])
    want = np.asarray(ref.decode_attention_ref(q, k, v, kv_len))
    np.testing.assert_allclose(out[live], want[live], atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("M", [1, 3, 4, 7])
def test_decode_small_kv_width_parity(M):
    """Caches narrower than a block: ``bk`` clamps to the cache width and
    the non-multiple tail is padded+masked — in BOTH decode kernels (the
    per-head reference kernel and the batched serving kernel)."""
    B, H, KV, hd = 2, 4, 2, 16
    q, k, v, _ = _inputs(B, M, H, KV, hd, seed=M)
    kv_len = jnp.asarray([M, max(1, M - 1)], jnp.int32)
    want = np.asarray(ref.decode_attention_ref(q, k, v, kv_len))
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    per_head = decode_attention_bhmd(q[:, 0], kt, vt, kv_len, bk=512)
    batched = batched_decode_attention_bhmd(q[:, 0], kt, vt, kv_len, bk=256)
    np.testing.assert_allclose(np.asarray(per_head), want[:, 0],
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(batched), want[:, 0],
                               atol=2e-3, rtol=2e-3)


def test_decode_dispatch_branch_uses_kernel():
    """layers._dispatch_attention routes the q_len==1 + kv_len decode
    shape to the batched kernel under pallas_enabled and to the jnp
    reference otherwise; both must agree."""
    from repro.models import layers as L

    B, M, H, KV, hd = 3, 48, 4, 2, 16
    q, k, v, kv_len = _inputs(B, M, H, KV, hd, seed=13)
    with pallas_enabled(False):
        want = L._dispatch_attention(q, k, v, causal=False, window=None,
                                     kv_len=kv_len)
    with pallas_enabled(True):
        out = L._dispatch_attention(q, k, v, causal=False, window=None,
                                    kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


# ---- end-to-end through the live engine -----------------------------------

def test_engine_decode_pallas_token_identical(model_zoo):
    """The full engine loop (chunked prefill + per-tick batched decode,
    both dispatched through the Pallas kernels in interpret mode) must
    produce identical tokens to the jnp reference path."""
    from repro.serving.engine import ServingEngine

    cfg, params = model_zoo("qwen2-1.5b")
    prompts = ["short", "a much longer prompt with many more words in it",
               "mid sized prompt here", "x"]

    def run(use_pallas: bool):
        with pallas_enabled(use_pallas):
            eng = ServingEngine(cfg, params, batch_slots=3, max_len=96,
                                prefill_chunk=8)
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            eng.run_until_done()
            assert all(r.done for r in reqs)
            return [tuple(r.output_ids) for r in reqs], eng

    want, _ = run(False)
    got, eng_pl = run(True)
    assert got == want
    assert eng_pl.stats["prefill_backend"] == "pallas"
    # the decode loop really ran batched multi-slot ticks
    assert eng_pl.stats["peak_active"] >= 2
    assert eng_pl.stats["tokens_out"] >= len(prompts) * 5


def test_device_sample_greedy_safe_denominator():
    """Greedy rows (temperature 0) must divide by the where-selected safe
    denominator, not by zero: no inf/NaN anywhere in the sample step even
    with padded-vocab -1e9 logits, under jax_debug_nans."""
    from repro.serving.engine import _device_sample

    logits = jnp.asarray([[1.0, 3.0, -1e9, 2.0],
                          [-1e9, -1e9, 0.5, 0.25],
                          [0.0, 0.0, 0.0, -1e9]], jnp.float32)
    temps = jnp.asarray([0.0, 0.7, 0.0], jnp.float32)
    jax.config.update("jax_debug_nans", True)
    try:
        out = jax.jit(_device_sample)(logits, jax.random.PRNGKey(0), temps)
        ids = np.asarray(out)
    finally:
        jax.config.update("jax_debug_nans", False)
    assert ids[0] == 1 and ids[2] == 0          # greedy rows == argmax
    assert 0 <= ids[1] < 4


def test_engine_greedy_decode_nan_free_under_debug_nans(model_zoo):
    """A greedy fleet through the live engine with jax_debug_nans on: the
    fused decode+sample and prefill+sample steps must be NaN/inf-free
    end to end."""
    from repro.serving.engine import ServingEngine

    cfg, params = model_zoo("qwen2-1.5b")
    jax.config.update("jax_debug_nans", True)
    try:
        eng = ServingEngine(cfg, params, batch_slots=2, max_len=64,
                            prefill_chunk=8)
        reqs = [eng.submit(p, max_new_tokens=4)
                for p in ["hello there", "tiny"]]
        eng.run_until_done()
    finally:
        jax.config.update("jax_debug_nans", False)
    assert all(r.done for r in reqs)


def test_engine_retraces_bounded_across_varied_length_fleet(model_zoo):
    """stats["jit_retraces"] must stay bounded for ANY prompt-length mix:
    every prefill signature comes off the static power-of-two bucket
    ladders (g <= slots; width <= chunk bucket; kv_width <= max_len
    ladder) and decode has one shape, so (a) a varied fleet stays under
    the ladder-size bound and (b) rerunning the same length mix on a
    FRESH engine adds ZERO new compiles (the lru-shared step pair is
    the whole point)."""
    from repro.serving.engine import ServingEngine

    cfg, params = model_zoo("qwen2-1.5b")

    def fleet(lengths, seed):
        # measure the DELTA this fleet adds to the lru-SHARED step cache:
        # other tests' engines share the (cfg, max_len, backend) key, so
        # the absolute count depends on test ordering, but what one fleet
        # mix ADDS is ladder-bounded regardless
        eng = ServingEngine(cfg, params, batch_slots=3, max_len=96,
                            prefill_chunk=8, seed=seed)
        eng._track_retraces()
        base = eng.stats["jit_retraces"]
        cbase = eng.stats["prefix_seed_compiles"]
        reqs = [eng.submit("word " * n, max_new_tokens=3) for n in lengths]
        eng.run_until_done()
        assert all(r.done for r in reqs)
        return (eng.stats["jit_retraces"] - base,
                eng.stats["prefix_seed_compiles"] - cbase)

    # ladder for this shape: g in {1,2,3}; width == 8 (chunk bucket);
    # kv_width in {8, 16, 32, 64, 96}; decode is one shape
    bound = 3 * 5 + 1
    lengths = [1, 3, 5, 9, 14, 22, 30, 38]
    n1, c1 = fleet(lengths, seed=0)
    assert n1 <= bound, n1
    # rerunning the SAME length mix on a fresh engine adds ZERO compiles
    # (the lru-shared step pair is the whole point); the "word "*n fleet
    # shares prefixes, so the prefix-seed copy ladder obeys the same
    # contract
    n2, c2 = fleet(lengths, seed=1)
    assert n2 == 0, (n1, n2)
    assert c2 == 0, (c1, c2)
