"""Cross-request KV prefix reuse: the bit-identity contract end to end.

Engine level — seeded shared-prefix fleets must produce token-identical
greedy outputs with reuse on vs off (the acceptance property), partial
(non-block-multiple) tails always prefill, a free slot whose lines a
borrower matched stays pinned until the seed copy launches, and a
borrower that re-leases its own source reuses lines in place with no
copy. Pool level — per-replica prefix indexes break least-loaded ties
(affinity never outranks load) and die with a crashed replica (failover
restarts from the prompt on a survivor). Fleet level — reuse on/off
serve the same prompt→answer map through the live FleetScheduler pump
loop, including under injected chaos (``CHAOS_SEED`` shifts the fault
universes like the rest of the chaos suite).
"""
import os

import pytest
from _prop import given, settings, st

from repro.data import tokenizer as tok
from repro.models import kvcache as KV
from repro.serving.engine import ServingEngine

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))

# a shared system prompt longer than one PREFIX_BLOCK (16 tokens ~ 15
# chars at one byte-token per char + BOS)
SYSTEM = "You are a careful assistant. Always reason step by step. "

# property tests can't take pytest fixtures through the _prop fallback's
# opaque wrapper signature, so they share one module-cached tiny model
_ZOO: dict = {}


def _lazy_zoo():
    if not _ZOO:
        import jax
        import jax.numpy as jnp

        from repro.configs import get_config
        from repro.models import model as M
        cfg = get_config("qwen2-1.5b").reduced()
        _ZOO["m"] = (cfg, M.init_params(cfg, jax.random.PRNGKey(0),
                                        dtype=jnp.float32))
    return _ZOO["m"]


def _eng(cfg, params, *, reuse, slots=3, max_len=96, chunk=8, seed=0,
         block=KV.PREFIX_BLOCK):
    return ServingEngine(cfg, params, batch_slots=slots, max_len=max_len,
                         prefill_chunk=chunk, seed=seed, prefix_reuse=reuse,
                         prefix_block=block)


def _run_fleet(eng, prompts, max_new=5):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_done()
    assert all(r.done for r in reqs)
    return [tuple(r.output_ids) for r in reqs]


# ---- kvcache primitives --------------------------------------------------

def test_prefix_block_hashes_chained_and_block_floored():
    ids = list(range(10, 10 + 40))
    hs = KV.prefix_block_hashes(ids, block=16)
    assert len(hs) == 2                        # 40 tokens -> 2 full blocks
    # chained: block 2's hash depends on block 1's content
    other = [99] + ids[1:]
    hs2 = KV.prefix_block_hashes(other, block=16)
    assert hs2[0] != hs[0] and hs2[1] != hs[1]
    # prefix property: same leading blocks -> same leading hashes
    assert KV.prefix_block_hashes(ids[:16], block=16) == hs[:1]
    assert KV.prefix_block_hashes(ids[:15], block=16) == []


def test_copy_prefix_matches_numpy_reference():
    import numpy as np
    import jax.numpy as jnp
    L, B, M, KVh, hd = 2, 4, 32, 2, 8
    rng = np.random.default_rng(0)
    k = rng.normal(size=(L, B, M, KVh, hd)).astype(np.float32)
    v = rng.normal(size=(L, B, M, KVh, hd)).astype(np.float32)
    src = np.asarray([0, 2], np.int32)
    dst = np.asarray([1, 3], np.int32)
    ln = np.asarray([16, 7], np.int32)
    want_k, want_v = k.copy(), v.copy()
    for g in range(2):
        want_k[:, dst[g], :ln[g]] = k[:, src[g], :ln[g]]
        want_v[:, dst[g], :ln[g]] = v[:, src[g], :ln[g]]
    got_k, got_v = (KV.copy_prefix(jnp.asarray(x), jnp.asarray(src),
                                   jnp.asarray(dst), jnp.asarray(ln),
                                   width=16) for x in (k, v))
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)


# ---- engine-level identity ----------------------------------------------

def test_shared_prefix_fleet_token_identical_and_saves(model_zoo):
    """Acceptance: a shared-system-prompt fleet is bit-identical with
    reuse on vs off, and reuse-on measurably skips prefill tokens."""
    cfg, params = model_zoo("qwen2-1.5b")
    prompts = [SYSTEM + t for t in
               ("solve part one", "now part two", "and the third part",
                "a fourth subtask", "finally the fifth", "one more")]
    off = _eng(cfg, params, reuse=False)
    want = _run_fleet(off, prompts)
    on = _eng(cfg, params, reuse=True)
    got = _run_fleet(on, prompts)
    assert got == want
    assert on.stats["prefix_hits"] > 0
    assert on.stats["prefill_tokens_saved"] > 0
    # the saving is exact: off prefills everything, on skips exactly what
    # it borrowed
    assert off.stats["prefill_tokens"] == \
        on.stats["prefill_tokens"] + on.stats["prefill_tokens_saved"]
    assert off.stats["prefix_hits"] == off.stats["prefill_tokens_saved"] == 0


def test_partial_block_tail_always_prefills(model_zoo):
    """A prompt equal to a cached prompt plus a sub-block tail (and one
    EXACTLY equal) still prefills >= 1 token and stays bit-identical."""
    cfg, params = model_zoo("qwen2-1.5b")
    base = SYSTEM + "alpha beta"
    prompts = [base, base + " x", base]        # exact duplicate included
    off = _eng(cfg, params, reuse=False, slots=1)
    want = _run_fleet(off, prompts)
    on = _eng(cfg, params, reuse=True, slots=1)
    got = _run_fleet(on, prompts)
    assert got == want
    assert on.stats["prefix_hits"] >= 1
    # the proper-prefix cap: even the exact duplicate prefilled its tail
    ids = tok.encode(base)
    cap = ((len(ids) - 1) // on.prefix_block) * on.prefix_block
    assert on.stats["prefix_hits"] == 2
    # the cap keeps every borrow a PROPER prefix: even the exact
    # duplicate prefilled at least one tail token
    assert 0 < on.stats["prefill_tokens_saved"] <= 2 * cap
    assert off.stats["prefill_tokens"] == \
        on.stats["prefill_tokens"] + on.stats["prefill_tokens_saved"]


def test_in_place_reuse_skips_copy(model_zoo):
    """slots=1: the borrower always re-leases its own source slot, so
    reuse fires with ZERO cross-slot copies."""
    cfg, params = model_zoo("qwen2-1.5b")
    eng = _eng(cfg, params, reuse=True, slots=1)
    _run_fleet(eng, [SYSTEM + "first question", SYSTEM + "second question"])
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_copies"] == 0
    assert eng.stats["prefill_tokens_saved"] > 0


def test_eviction_pinned_while_borrowed(model_zoo):
    """A free source slot matched by a borrower is pinned: a co-admitted
    request must skip it (landing on the next free slot) and the pins
    clear once the batched seed copy launches."""
    cfg, params = model_zoo("qwen2-1.5b")
    eng = _eng(cfg, params, reuse=True, slots=3)
    # wave 1: fill slots 0 and 1; slot 1 caches the shared prefix
    _run_fleet(eng, ["junk padding text unrelated", SYSTEM + "seed prompt"])
    assert eng.stats["prefix_copies"] == 0
    # wave 2: two requests admitted in one pass. First-fit puts the
    # borrower on slot 0; its best source is FREE slot 1, which must be
    # pinned so the second request lands on slot 2, not slot 1.
    b = eng.submit(SYSTEM + "borrower tail", max_new_tokens=4)
    c = eng.submit("other unrelated words", max_new_tokens=4)
    eng._admit()
    assert eng.active[0] is b
    assert eng.active[1] is None               # pinned, skipped
    assert eng.active[2] is c
    assert eng._pinned == {1}
    assert eng._pending_copy == [(0, 1, eng.stats["prefill_tokens_saved"])]
    eng.run_until_done()
    assert b.done and c.done
    assert not eng._pinned and not eng._pending_copy
    assert eng.stats["prefix_copies"] == 1


def test_cancel_mid_prefill_releases_pin(model_zoo):
    """Cancelling a borrower before its seed copy launches drops the
    pending copy and frees the pinned source for the next admit."""
    cfg, params = model_zoo("qwen2-1.5b")
    eng = _eng(cfg, params, reuse=True, slots=2)
    _run_fleet(eng, ["junk padding text unrelated", SYSTEM + "seed prompt"])
    b = eng.submit(SYSTEM + "borrower tail", max_new_tokens=4)
    eng._admit()
    assert eng._pinned == {1}
    assert eng.cancel(b)
    assert not eng._pinned and not eng._pending_copy
    d = eng.submit("fresh request takes any slot", max_new_tokens=3)
    eng.run_until_done()
    assert d.done


@settings(max_examples=int(os.environ.get("PROP_MAX_EXAMPLES", "6")),
          deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 4), st.integers(1, 3))
def test_prop_seeded_shared_prefix_fleets_identical(seed, slots, n_groups):
    """Property: random fleets of shared-prefix groups (random group
    sizes, tails, and interleaving) are token-identical with reuse on
    vs off and never decode a different token count."""
    import random
    cfg, params = _lazy_zoo()
    rng = random.Random(seed)
    prompts = []
    for g in range(n_groups):
        head = f"shared context {g} " * rng.randint(2, 4)
        for i in range(rng.randint(1, 4)):
            prompts.append(head + f"tail {i} " * rng.randint(1, 6))
    rng.shuffle(prompts)
    off = _eng(cfg, params, reuse=False, slots=slots)
    want = _run_fleet(off, prompts, max_new=4)
    on = _eng(cfg, params, reuse=True, slots=slots)
    got = _run_fleet(on, prompts, max_new=4)
    assert got == want
    assert on.stats["tokens_out"] == off.stats["tokens_out"]
    assert off.stats["prefill_tokens"] == \
        on.stats["prefill_tokens"] + on.stats["prefill_tokens_saved"]


# ---- pool level ----------------------------------------------------------

def test_pool_prefix_affinity_breaks_load_ties(model_zoo):
    """At equal load, submit with a matching hint lands on the replica
    whose index holds the prefix — overriding the lowest-index tie-break
    but never outranking load."""
    from repro.serving.pool import EnginePool
    cfg, params = model_zoo("qwen2-1.5b")
    pool = EnginePool.replicate(cfg, params, replicas=2, batch_slots=2,
                                max_len=96, prefill_chunk=8)
    a = pool.submit("junk padding text unrelated", max_new_tokens=3)
    b = pool.submit(SYSTEM + "seed prompt", max_new_tokens=3)
    assert b._engine is pool.engines[1]        # least-loaded tie-break
    pool.run_until_done()
    # equal (zero) load: affinity must route the sharer to replica 1
    c = pool.submit(SYSTEM + "follow-up question", max_new_tokens=3)
    assert c._engine is pool.engines[1]
    pool.run_until_done()
    assert c.done
    assert pool.engines[1].stats["prefix_hits"] >= 1
    # ...but load outranks affinity: saturate replica 1 and the next
    # sharer must go to the idle replica 0
    busy = [pool.engines[1].submit(f"fill {i}", max_new_tokens=3)
            for i in range(2)]
    d = pool.submit(SYSTEM + "another sharer", max_new_tokens=3)
    assert d._engine is pool.engines[0]
    pool.run_until_done()
    assert d.done and all(r.done for r in busy)


def test_pool_failover_restarts_on_survivor_with_warm_index(model_zoo):
    """A dead replica's prefix index dies with its KV pool: failed-over
    requests restart from the prompt on the survivor and can re-match
    whatever the SURVIVOR's index holds."""
    from repro.serving.faults import FaultInjector, FaultPlan
    from repro.serving.pool import EnginePool
    cfg, params = model_zoo("qwen2-1.5b")
    pool = EnginePool.replicate(cfg, params, replicas=2, batch_slots=2,
                                max_len=96, prefill_chunk=8)
    # warm BOTH indexes with the shared prefix, then kill replica 1 on
    # its 2nd step while it serves a sharer
    pool.engines[0].submit(SYSTEM + "warm zero", max_new_tokens=3)
    pool.engines[1].submit(SYSTEM + "warm one", max_new_tokens=3)
    pool.run_until_done()
    inj = FaultInjector(FaultPlan(seed=CHAOS_SEED, crash_replica=((1, 2),)))
    inj.wrap_pool(pool)
    reqs = [pool.submit(SYSTEM + f"sharer number {i}", max_new_tokens=4)
            for i in range(4)]
    pool.run_until_done()
    assert all(r.done and len(r.output_ids) == 4 for r in reqs)
    assert pool.health == ["healthy", "dead"]
    assert pool.pool_stats["failovers"] >= 1
    # the survivor's index served reuse hits for the failed-over restarts
    assert pool.engines[0].stats["prefix_hits"] >= 1
    assert pool.stats["prefix_hits"] >= 1      # aggregated engine-shaped


# ---- fleet level ---------------------------------------------------------

def _fleet_answers(model_zoo, *, reuse, faults=None, retry=None, n=4):
    from repro.core.hybridflow import StaticPolicy
    from repro.core.planner import SyntheticPlanner
    from repro.data.tasks import WorldModel, gen_benchmark
    from repro.serving.engine import JAXExecutor
    from repro.serving.runtime import ServingConfig, ServingRuntime
    cfg, params = model_zoo("qwen2-1.5b")
    wm = WorldModel()
    edge = JAXExecutor(_eng(cfg, params, reuse=reuse, slots=2, max_len=128),
                       wm, cloud=False)
    cloud = JAXExecutor(_eng(cfg, params, reuse=reuse, slots=2, max_len=128,
                             seed=1),
                        wm, cloud=True, price_out=3.2e-5)
    rt = ServingRuntime(edge, cloud, StaticPolicy(1),
                        planner=SyntheticPlanner(),
                        config=ServingConfig(max_inflight=6, pump=True,
                                             faults=faults, retry=retry))
    rep = rt.serve(gen_benchmark("gpqa", n))
    stats = rep.stats
    # greedy answers depend only on (prompt, model), NOT on dispatch
    # order or slot assignment, so a prompt->answer map is the right
    # identity key across scheduling differences
    answers = sorted((r.qid, s.sid, s.answer) for r in rep.results
                     for s in r.results.values())
    return answers, stats


def test_fleet_reuse_on_off_same_answers(model_zoo):
    """The live FleetScheduler pump loop (DAG hints armed) serves the
    same per-subtask answers with reuse on and off, and reuse-on
    reports hits from the executors' shared query context."""
    on, stats_on = _fleet_answers(model_zoo, reuse=True)
    off, stats_off = _fleet_answers(model_zoo, reuse=False)
    assert on == off
    hits = stats_on.get("edge_prefix_hits", 0) + \
        stats_on.get("cloud_prefix_hits", 0)
    assert hits > 0
    assert stats_off.get("edge_prefix_hits", 0) == 0
    assert stats_off.get("cloud_prefix_hits", 0) == 0


def test_fleet_reuse_under_chaos_completes(model_zoo):
    """Prefix hints survive retry and degradation re-dispatch: a chaos
    fleet (submit failures, recovery armed) completes every subtask with
    reuse on, and with a deterministic submit_fail-only plan the answers
    match the reuse-off run under the SAME plan."""
    from repro.core.scheduler import RetryPolicy
    from repro.serving.faults import FaultPlan
    plan = dict(seed=CHAOS_SEED + 11, submit_fail_rate=0.15)
    retry = RetryPolicy(max_retries=3, timeout_s=None)
    on, stats_on = _fleet_answers(model_zoo, reuse=True,
                                  faults=FaultPlan(**plan), retry=retry)
    off, _ = _fleet_answers(model_zoo, reuse=False,
                            faults=FaultPlan(**plan), retry=retry)
    assert on == off
    assert len(on) > 0
