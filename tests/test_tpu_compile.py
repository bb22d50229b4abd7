"""Compile the serving path for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib: it compiles for a topology
that is described and not attached. These tests compile the two Pallas
serving kernels with ``interpret=False`` and the engine's fused decode
and chunk-prefill step programs (``serving.engine._jit_steps``) at the
published qwen2-1.5b widths in bfloat16, so tiling, VMEM and memory
refusals that interpret mode passes surface here. Nothing runs; a
compile that passes is not a chip run.

The topology is described inside the module fixture — never at import —
because only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dispatch
from repro.kernels.batched_decode_attention import (
    batched_decode_attention_bhmd)
from repro.kernels.ragged_prefill_attention import (
    ragged_prefill_attention_bhsd)
from repro.models import model as M
from repro.serving.engine import _jit_steps

ARCH = "qwen2-1.5b"
H, KV, HD = 12, 2, 128          # qwen2-1.5b attention heads
G, S, W = 4, 64, 1024           # ragged prefill: rows, chunk, kv_width
SLOTS, MAX_LEN = 4, 2048        # serving engine: KV slots x lines
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache here; keep it out of the cache entirely
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _placed(sharding, tree):
    return jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype), tree)


def test_ragged_prefill_kernel_compiles(one_chip):
    bf = jnp.bfloat16
    args = (_on(one_chip, (G, H, S, HD), bf), _on(one_chip, (G, KV, W, HD), bf),
            _on(one_chip, (G, KV, W, HD), bf), _on(one_chip, (G,), jnp.int32),
            _on(one_chip, (G,), jnp.int32))
    fn = jax.jit(lambda q, k, v, pos0, take: ragged_prefill_attention_bhsd(
        q, k, v, pos0, take, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_batched_decode_kernel_compiles(one_chip):
    bf = jnp.bfloat16
    args = (_on(one_chip, (SLOTS, H, HD), bf),
            _on(one_chip, (SLOTS, KV, MAX_LEN, HD), bf),
            _on(one_chip, (SLOTS, KV, MAX_LEN, HD), bf),
            _on(one_chip, (SLOTS,), jnp.int32))
    fn = jax.jit(lambda q, k, v, kv_len: batched_decode_attention_bhmd(
        q, k, v, kv_len, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_serving_step_compiles_at_published_widths(one_chip, monkeypatch,
                                                   step):
    """The engine's jitted step at full width, Pallas dispatched, fits
    one chip's HBM: arguments (weights + KV pool) plus temporaries."""
    # this process's backend is the CPU; the described chip compiles them
    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    cfg = get_config(ARCH)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (28, 1536, 151936)
    params = _placed(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _placed(one_chip, M.init_cache_specs(cfg, SLOTS, MAX_LEN,
                                                 jnp.bfloat16))
    key = _placed(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    pos = _on(one_chip, (SLOTS,), jnp.int32)
    with dispatch.pallas_enabled(True):
        decode_step, prefill_step = _jit_steps(cfg, MAX_LEN, True)
        if step == "decode":
            lowered = decode_step.lower(
                params, _on(one_chip, (SLOTS, 1), jnp.int32), pos, cache, key,
                _on(one_chip, (SLOTS,), jnp.float32), pos)
        else:
            rows = _on(one_chip, (G,), jnp.int32)
            lowered = prefill_step.lower(
                params, _on(one_chip, (G, S), jnp.int32), rows, rows, rows,
                pos, cache, key, _on(one_chip, (G,), jnp.float32), MAX_LEN)
        compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (step, used)
