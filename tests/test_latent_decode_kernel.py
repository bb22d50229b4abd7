"""The latent decode kernel (``kernels/latent_decode_attention.py``)
against its reference twin ``mla.attend_absorbed``, in interpret mode on
the CPU.

The kernel reads a stacked pool (c_kv [L, B, M, C], k_pe lines-minor
[L, B, R, M]) whose width M is not a multiple of the block, one row at each of ``kv_len`` 0, 1, ``bk``,
``bk + 1`` and M. Every line at or past a row's ``kv_len``, and every
line of the other layers, is poisoned in the kernel's pool (NaN or 1e4)
and left clean in the reference's: a dead block or another layer that
entered the result would show.

Tolerances: float32 sums in another order agree to ~1e-6 of values of
order 1 (2e-5 leaves room). In bfloat16 both sides round the
probabilities to bfloat16 before ``P . c_kv`` (the reference after
normalising them, the kernel before), a relative 4e-3 on each term of a
weighted mean of values of order 1; 2e-2 of the largest output leaves
room for that and is far under what a wrong line gives (order 1).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import mla as MLA
from repro.models import model as M

BK = 16
L_, B, LINES = 3, 5, 3 * BK + 8
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.fixture(scope="module")
def attn():
    cfg = get_config("deepseek-v2-lite").reduced()
    assert (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim) == (4, 64, 16)
    params = M.init_params(cfg, jax.random.PRNGKey(5), dtype=jnp.float32)
    return cfg, jax.tree.map(lambda x: x[1], params["layers"]["attn"])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("poison", [float("nan"), 1e4], ids=["nan", "1e4"])
@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_only_the_live_lines_of_its_layer(attn, dtype, poison,
                                                       layer):
    """The kernel writes each row's new k_pe line at ``kv_len - 1`` and
    attends the row's first ``kv_len`` lines of its layer, as the
    reference does on a clean pool with the same line written; the pool
    comes back with that line alone changed."""
    cfg, a = attn
    H, N, R, C = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.kv_lora_rank)
    ks = jax.random.split(jax.random.PRNGKey(layer), 5)
    q_nope = jax.random.normal(ks[0], (B, 1, H, N)).astype(dtype)
    q_pe = jax.random.normal(ks[1], (B, 1, H, R)).astype(dtype)
    ckv = jax.random.normal(ks[2], (L_, B, LINES, C)).astype(dtype)
    kpe = jax.random.normal(ks[3], (L_, B, R, LINES)).astype(dtype)
    k_new = jax.random.normal(ks[4], (B, 1, R)).astype(dtype)
    kv_len = jnp.asarray([0, 1, BK, BK + 1, LINES], jnp.int32)
    rows = jnp.arange(1, B)                 # kv_len > 0: these write
    line = (jnp.arange(LINES)[None, :] < kv_len[:, None])
    live = (jnp.arange(L_) == layer)[:, None, None] & line[None]
    bad = jnp.asarray(poison, dtype)
    ap = jax.tree.map(lambda x: x.astype(dtype), a)
    got, got_kpe = MLA.attend_absorbed_kernel(
        ap, cfg, q_nope, q_pe, k_new, jnp.where(live[..., None], ckv, bad),
        jnp.where(live[:, :, None], kpe, bad), layer, kv_len, bk=BK,
        interpret=True)
    written = kpe.at[layer, rows, :, kv_len[1:] - 1].set(k_new[1:, 0])
    want = MLA.attend_absorbed(ap, cfg, q_nope, q_pe, ckv[layer],
                               jnp.swapaxes(written[layer], 1, 2), kv_len)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert not got[0].any()                 # kv_len 0 reads nothing
    np.testing.assert_allclose(
        got, want, rtol=0, atol=TOL[dtype] * float(np.abs(want).max()))
    want_kpe = jnp.where(live[:, :, None], kpe, bad)
    want_kpe = want_kpe.at[layer, rows, :, kv_len[1:] - 1].set(k_new[1:, 0])
    np.testing.assert_array_equal(np.asarray(got_kpe, np.float32),
                                  np.asarray(want_kpe, np.float32))


def test_block_fits_the_pool():
    from repro.kernels.latent_decode_attention import BLOCK, block_lines
    assert block_lines(4096) == BLOCK
    assert block_lines(BLOCK // 2) == BLOCK // 2
