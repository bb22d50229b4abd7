"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned architecture runs one forward + one train step on CPU with
correct shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import model as M
from repro.training.loop import make_train_step, init_train_state


def _batch(cfg, B=2, S=16, key=None):
    key = key or jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.1 * jnp.ones(
            (B, cfg.n_image_patches, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        batch["frames"] = 0.1 * jnp.ones(
            (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    batch = _batch(cfg)
    logits, aux = M.forward(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.padded_vocab())
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    step = jax.jit(make_train_step(cfg))
    batch = _batch(cfg)
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # a second step must also be finite (optimizer state sanity)
    params, opt, metrics2 = step(params, opt, batch)
    assert np.isfinite(float(metrics2["loss"]))


def test_param_count_sanity():
    """Analytic parameter counts land near the advertised scales."""
    cases = {
        "mistral-large-123b": (110e9, 135e9),
        "mixtral-8x7b": (42e9, 52e9),
        "qwen2-1.5b": (1.2e9, 2.2e9),
        "internlm2-1.8b": (1.4e9, 2.4e9),
        "kimi-k2-1t-a32b": (0.8e12, 1.3e12),
        "deepseek-v2-lite": (15.5e9, 15.9e9),   # published: 15.7B
    }
    for arch, (lo, hi) in cases.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params():
    cfg = get_config("kimi-k2-1t-a32b")
    assert cfg.param_count(active_only=True) < 0.1 * cfg.param_count()


def test_vocab_padding():
    cfg = get_config("whisper-medium")
    assert cfg.padded_vocab() % 256 == 0
    assert cfg.padded_vocab() >= cfg.vocab_size
