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
import re

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


def test_decode_step_touches_the_kv_pool_in_place(one_chip):
    """The XLA decode program (the engine's default backend) writes each
    token into the donated pool and reads the stored KV heads grouped: its
    temporaries hold no copy of the pool, no op repeats K/V to the query
    heads, and nothing outside the layer loop rewrites a whole cache."""
    slots = 2
    cfg = get_config(ARCH)
    bf = jnp.bfloat16
    params = _placed(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=bf)))
    cache = _placed(one_chip, M.init_cache_specs(cfg, slots, MAX_LEN, bf))
    key = _placed(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    rows = _on(one_chip, (slots,), jnp.int32)
    with dispatch.pallas_enabled(False):
        decode_step, _ = _jit_steps(cfg, MAX_LEN, False)
        compiled = decode_step.lower(
            params, _on(one_chip, (slots, 1), jnp.int32), rows, cache, key,
            _on(one_chip, (slots,), jnp.float32), rows).compile()
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool / 100, (temp, pool)
    hlo = compiled.as_text()
    assert f"[{slots},{MAX_LEN},{KV},{H // KV},{HD}]" not in hlo
    whole = f"bf16[{cfg.n_layers},{slots},{MAX_LEN},{KV},{HD}]"
    entry = hlo[hlo.index("\nENTRY"):]
    made = {m.group(1) for m in re.finditer(
        re.escape(whole) + r"\S* ([\w-]+)\(", entry)}
    assert made <= {"parameter", "get-tuple-element", "tuple", "bitcast"}, \
        made


def test_latent_decode_step_reads_the_pool_in_place(one_chip, monkeypatch):
    """The deepseek-v2-lite decode program as a TPU process compiles it
    (published widths, bf16, 4,096 lines): each layer's absorbed attention
    is one Mosaic kernel that reads the layer-stacked latent pool in place
    and writes the k_pe line. Its temporaries hold no copy of the pool, no
    op anywhere copies or transposes a whole pool array, and the entry
    computation makes pool-shaped buffers only as parameters, tuple parts,
    bitcasts and layer 0's in-place line writes and kernel (the one dense
    layer lies outside the layer loop). 8 slots: at 2 and 4 the compiler moves the
    28-57 MB k_pe pool into the chip's fast memory and back around each
    layer, which a served pool (32 slots, 453 MB) is too large for."""
    monkeypatch.setattr(dispatch, "interpret_mode", lambda: False)
    slots, max_len, bf = 8, 4096, jnp.bfloat16
    cfg = get_config("deepseek-v2-lite").variant(n_experts_held=8)
    params = _placed(one_chip, jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0), dtype=bf)))
    cache = _placed(one_chip, M.init_cache_specs(cfg, slots, max_len, bf))
    key = _placed(one_chip, jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    rows = _on(one_chip, (slots,), jnp.int32)
    decode_step, _ = _jit_steps(cfg, max_len, False)
    compiled = decode_step.lower(
        params, _on(one_chip, (slots, 1), jnp.int32), rows, cache, key,
        _on(one_chip, (slots,), jnp.float32), rows).compile()
    pool = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool / 100, (temp, pool)
    hlo = compiled.as_text()
    assert re.search(r"%latent_decode_attention\S* = .* custom-call\(", hlo)
    assert 'custom_call_target="tpu_custom_call"' in hlo
    L_, C, R = cfg.n_layers, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    shapes = [f"bf16[{L_},{slots},{max_len},{C}]",
              f"bf16[{L_},{slots},{R},{max_len}]",
              f"bf16[{L_},{slots},{max_len},{R}]"]
    entry = hlo[hlo.index("\nENTRY"):]
    for whole in shapes:
        ops = r"\S* ([\w-]+)\("
        moved = {m.group(1) for m in re.finditer(re.escape(whole) + ops, hlo)}
        assert not moved & {"copy", "copy-start", "copy-done", "transpose"}, \
            (whole, moved)
        made = {m.group(1) for m in re.finditer(re.escape(whole) + ops, entry)}
        assert made <= {"parameter", "get-tuple-element", "tuple", "bitcast",
                        "fusion", "custom-call"}, (whole, made)
