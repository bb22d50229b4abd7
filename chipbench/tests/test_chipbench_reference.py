"""The reference decoder against the program's own serving steps at CPU
sizes, and the traffic generator's determinism."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import drive_common as C
from chipbench import reference, traffic, weights
from chipbench.tests import tiny


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_chunked_prefill_and_cached_decode(tied):
    """Prefill in chunks into a slot of the pooled cache, then decode
    through the cache, in float32: every logit agrees with the reference's
    full forward pass over the same tokens, with the output head tied to
    the embedding (as served) or apart."""
    from repro.models import model as M
    model = tiny.small_model("qwen2-1.5b")
    model["tie_word_embeddings"] = tied
    model["overrides"]["tie_embeddings"] = tied
    model["torch_dtype"] = "float32"
    model["overrides"]["dtype"] = "float32"
    cfg = C.model_config(model)
    sz = weights.Sizes.of(model)
    words = weights.seed_words(2**40 + 3)
    params = C.program_params(cfg, sz, words)
    slots, max_len, chunk, slot = 3, 128, 16, 1
    cache = M.init_cache(cfg, slots, max_len, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, sz.vocab, 37).tolist()
    got = []
    for off in range(0, len(prompt), chunk):
        take = min(chunk, len(prompt) - off)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = prompt[off:off + take]
        logits, cache = M.serve_prefill_chunk(
            params, cfg, jnp.asarray(toks), cache,
            jnp.asarray([slot], jnp.int32), jnp.asarray([off], jnp.int32),
            jnp.asarray([take], jnp.int32), kv_width=max_len)
    got.append(np.asarray(logits[0, 0]))
    seq = list(prompt)
    for step in range(6):
        nxt = int(np.argmax(got[-1][:sz.vocab]))
        seq.append(nxt)
        tok = np.zeros((slots, 1), np.int32)
        tok[slot, 0] = nxt
        pos = np.full(slots, max_len - 1, np.int32)
        pos[slot] = len(seq) - 1
        logits, cache = M.serve_decode(params, cfg, jnp.asarray(tok),
                                       jnp.asarray(pos), cache)
        got.append(np.asarray(logits[slot, 0]))
    w = weights.canonical(sz, words, jnp.float32)
    T = reference.pad_to(len(seq))
    ids = np.zeros(T, np.int32)
    ids[:len(seq)] = seq
    want = len(prompt) - 1 + np.arange(len(got))
    ref = np.asarray(reference.logits_at(sz, w, jnp.asarray(ids),
                                         jnp.asarray(want, np.int32)))
    got = np.stack(got)[:, :sz.vocab]
    np.testing.assert_allclose(got, ref, rtol=0, atol=2e-4)


def test_canonical_weights_are_one_program_per_config_and_seeded():
    sz = weights.Sizes.of(tiny.small_model("qwen2-1.5b", vocab=320))
    before = weights.canonical._cache_size()
    a = weights.canonical(sz, weights.seed_words(2**33 + 1), jnp.bfloat16)
    b = weights.canonical(sz, weights.seed_words(2**33 + 1), jnp.bfloat16)
    c = weights.canonical(sz, weights.seed_words(2**33 + 2), jnp.bfloat16)
    assert all(bool(jnp.array_equal(a[k], b[k])) for k in a)
    assert not bool(jnp.array_equal(a["wq"], c["wq"]))
    assert weights.canonical._cache_size() == before + 1
    assert {x.dtype for x in jax.tree.leaves(a)} == {jnp.dtype("bfloat16")}


def test_traffic_is_a_function_of_the_seed():
    """The trace is drawn from the mix's own ``trace_seed``: one trace
    for every run of the mix, and another order of the same gaps under
    another trace seed."""
    mix = dict(tiny.FLEET_MIX, rate=20.0, trace_seed=2**31 + 12345)
    a = traffic.arrivals(mix, 5.0)
    assert a == traffic.arrivals(dict(mix), 5.0)
    c = traffic.arrivals(dict(mix, trace_seed=2**31 + 12346), 5.0)
    assert a != c and len(a) == len(c) == 110
    assert all(0 < t < 5.5 for t in a + c) and a == sorted(a)
    # the window holds the same arrivals under either trace seed
    lo, hi = traffic.window_of(mix, 5.0)
    due_a = [t for t in a if lo <= t < hi]
    due_c = [t for t in c if lo <= t < hi]
    assert len(due_a) == len(due_c) == traffic.counts(mix, 5.0)[1] == 100
    np.testing.assert_allclose(sorted(np.diff([lo] + due_a)),
                               sorted(np.diff([lo] + due_c)), rtol=1e-9)


def test_fleet_queries_are_one_pool_in_a_seeded_order():
    from repro.data.tasks import gen_benchmark
    mix = dict(tiny.FLEET_MIX, rate=2.0, trace_seed=2**33 + 1)
    n_pre, n_win = traffic.counts(mix, 5.0)
    a, wa = traffic.fleet_queries(mix, 5.0, gen_benchmark, 2)
    b, _ = traffic.fleet_queries(dict(mix), 5.0, gen_benchmark, 2)
    c, _ = traffic.fleet_queries(dict(mix, trace_seed=2**33 + 2), 5.0,
                                 gen_benchmark, 2)
    ids = [q.qid for q in a]
    assert len(ids) == n_pre + n_win == 11
    assert ids == [q.qid for q in b]
    # the window's queries come first in turn from the benchmarks
    names = [q.benchmark for q in a[n_pre:]]
    assert sorted(names) == sorted((mix["benchmarks"] * 3)[:n_win])
    assert [q.benchmark for q in c[n_pre:]] != names or \
        [q.qid for q in c] != ids
    assert len(wa) == 2 and not {q.qid for q in wa} & set(ids)
