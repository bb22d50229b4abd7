"""The reference decoder against the program's own serving steps at CPU
sizes, the dense family against values recorded from the functions it
binds, and the traffic generator's determinism."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, registry, traffic, weights
from chipbench.tests import tiny

DENSE = registry.load_family("dense")


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
    cfg = DENSE.served_config(model)
    sz = DENSE.sizes(model)
    words = weights.seed_words(2**40 + 3)
    params = DENSE.program_params(cfg, sz, words)
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
    w = DENSE.canonical(sz, words, jnp.float32)
    T = reference.pad_to(len(seq), DENSE.Q_BLOCK)
    ids = np.zeros(T, np.int32)
    ids[:len(seq)] = seq
    want = len(prompt) - 1 + np.arange(len(got))
    ref = np.asarray(DENSE.logits_at(sz, w, jnp.asarray(ids),
                                     jnp.asarray(want, np.int32), "f32"))
    got = np.stack(got)[:, :sz.vocab]
    np.testing.assert_allclose(got, ref, rtol=0, atol=2e-4)


# Recorded from the benchmark's weights.canonical and reference.logits_at
# before they were bound into families/dense.py: per tied head, the
# sha256 of the bfloat16 weights, and per mode rows 0 and 7 of the logits
# (first four entries) and the sum of their magnitudes.
RECORDED = {
    True: ("1c33d5d7ff056dfb9ba77f8d8d59e7f9212dfc0574ae7fb29883a1c07c4e40bf",
           {"f32": ([-0.017116, -0.008548, 0.183634, -0.395085,
                     0.215384, -0.114637, -0.060095, -0.489835], 503.0651),
            "fp8": ([-0.009678, 0.011302, 0.185876, -0.418715,
                     0.21576, -0.094918, -0.033898, -0.463633], 503.6856)}),
    False: ("c53beeb0f4087fa73c282ee0a25c55bc41c9003b2fb47521a176d955c9387034",
            {"f32": ([0.296028, -0.297425, 0.06704, 0.296997,
                      0.125374, -0.212211, 0.021344, 0.028692], 515.7408),
             "fp8": ([0.275953, -0.343768, 0.069454, 0.363982,
                      0.099555, -0.189922, 0.064717, 0.033798], 513.3486)}),
}


@pytest.mark.parametrize("tied", [True, False])
def test_dense_family_draws_and_computes_as_before(tied):
    """Same seed, same tensors bit for bit, and the same reference logits.
    The logits are held to float32 rounding, not to the bit: XLA's CPU
    matrix product splits its sums by the number of threads."""
    import hashlib
    model = json.loads((registry.PKG / "configs" / "qwen2-1.5b.json")
                       .read_text())
    model.update(tiny.SMALL, vocab_size=352, tie_word_embeddings=tied)
    sz = DENSE.sizes(model)
    w = DENSE.canonical(sz, weights.seed_words(2**40 + 7), jnp.bfloat16)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).view(np.uint16).tobytes())
    digest, logits = RECORDED[tied]
    assert h.hexdigest() == digest
    ids = np.zeros(DENSE.Q_BLOCK, np.int32)
    ids[:40] = (np.arange(40) * 37) % 300 + 16
    want = jnp.arange(30, 38, dtype=jnp.int32)
    for mode, (head, total) in logits.items():
        lg = np.asarray(DENSE.logits_at(sz, w, jnp.asarray(ids), want, mode))
        np.testing.assert_allclose(lg[[0, 7], :4].ravel(), head, rtol=0,
                                   atol=2e-5)
        assert np.abs(lg).astype(np.float64).sum() == \
            pytest.approx(total, rel=1e-5)


@pytest.mark.parametrize("tied", [True, False])
def test_silent_head_rows_are_zero_and_never_the_greedy_token(tied):
    """A config's ``silent_head_rows`` zero those rows of the output head
    and nothing else: every other tensor and row is drawn as without
    them, and the reference's logit of a silent id is 0, under the best."""
    model = tiny.small_model("qwen2-1.5b", vocab=336)
    model["tie_word_embeddings"] = tied
    words = weights.seed_words(2**40 + 11)
    plain = weights.canonical(weights.Sizes.of(model), words, jnp.bfloat16)
    sz = weights.Sizes.of(dict(model, silent_head_rows=[2, 5]))
    assert sz.silent == (2, 5)
    w = weights.canonical(sz, words, jnp.bfloat16)
    head = "embed" if tied else "lm_head"
    keep = np.setdiff1d(np.arange(sz.vocab), [2, 5])
    for k in plain:
        a, b = np.asarray(plain[k]), np.asarray(w[k])
        if k == head:
            assert not b[[2, 5]].any() and a[[2, 5]].any()
            a, b = a[keep], b[keep]
        np.testing.assert_array_equal(a, b)
    ids = np.zeros(DENSE.Q_BLOCK, np.int32)
    ids[:40] = (np.arange(40) * 37) % 300 + 16
    lg = np.asarray(DENSE.logits_at(sz, w, jnp.asarray(ids),
                                    jnp.arange(8, 40, dtype=jnp.int32),
                                    "f32"))
    assert not lg[:, [2, 5]].any()
    assert (lg.max(axis=1) > 0).all()


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


@pytest.mark.parametrize("trace_seed", [0, 2**31 + 77])
def test_engine_lengths_stay_inside_their_clip_ranges(trace_seed):
    """Every prompt and answer length lies in its mix's clip range; the
    burst is due at once, the rest in order; another trace seed draws the
    same lengths in another order; prompt ids are the seed's."""
    real = json.loads((registry.PKG / "traffic" / "decode-reasoning.json")
                      .read_text())
    for mix in (dict(real, trace_seed=trace_seed),
                dict(tiny.ENGINE_MIX, trace_seed=trace_seed)):
        reqs = traffic.engine_requests(mix, 51.0)
        other = traffic.engine_requests(dict(mix, trace_seed=5), 51.0)
        burst = int(mix["burst"])
        assert len(reqs) == burst + sum(traffic.counts(mix, 51.0))
        assert [r.due for r in reqs[:burst]] == [0.0] * burst
        assert [r.due for r in reqs] == sorted(r.due for r in reqs)
        for key in ("prompt", "answer"):
            got = [getattr(r, key) for r in reqs]
            assert mix[key]["min"] <= min(got) and max(got) <= mix[key]["max"]
            assert sorted(got) == sorted(getattr(r, key) for r in other)
    answers = [r.answer for r in traffic.engine_requests(real, 51.0)]
    assert np.mean(answers) == pytest.approx(1207, rel=0.01)
    ids = traffic.prompt_ids(reqs, 2**40 + 1, 1000)
    assert [len(x) for x in ids] == [r.prompt for r in reqs]
    assert min(map(min, ids)) >= traffic.FIRST_ID and max(map(max, ids)) < 1000
    assert ids == traffic.prompt_ids(reqs, 2**40 + 1, 1000)
    assert ids != traffic.prompt_ids(reqs, 2**40 + 2, 1000)
