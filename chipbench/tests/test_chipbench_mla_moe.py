"""Family ``mla_moe`` (deepseek-v2-lite) at a CPU size: its plain
reference against the program's chunked prefill and cached decode, the
expert share against the uncut layer, a tiny engine cell through the
harness, and the three readers it brings against hand-made traces and
counters.

Tolerances: the program and the reference both compute in float32 here;
they differ in the order of their sums (~1e-6 on logits of order 1), so
2e-4 on logits and 2e-5 on one expert layer leave that room and stay far
under the error of bfloat16 in place of float32 (~1e-2)."""
from __future__ import annotations

import json
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import e2e, registry, weights
from chipbench import run as R
from chipbench.recorder import EngineLog, Recorder, Tracked
from chipbench.tests import tiny, tiny_mla

FAM = registry.load_family("mla_moe")


def _f32(model):
    model = dict(model, torch_dtype="float32")
    model["overrides"] = dict(model["overrides"], dtype="float32")
    return model


def test_reference_matches_chunked_prefill_and_cached_decode():
    """Prefill in chunks into a slot of the latent pool, then decode
    through it, in float32: every logit agrees with the reference's full
    forward pass (per-head K/V decompressed, held experts in a masked
    loop) over the same tokens."""
    from repro.models import model as M
    model = _f32(tiny_mla.small_model())
    cfg = FAM.served_config(model)
    sz = FAM.sizes(model)
    words = weights.seed_words(2**40 + 5)
    params = FAM.program_params(cfg, sz, words)
    slots, max_len, chunk, slot = 3, 128, 16, 1
    cache = M.init_cache(cfg, slots, max_len, dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(16, sz.vocab, 37).tolist()
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
    for _ in range(6):
        seq.append(int(np.argmax(got[-1][:sz.vocab])))
        tok = np.zeros((slots, 1), np.int32)
        tok[slot, 0] = seq[-1]
        pos = np.full(slots, max_len - 1, np.int32)
        pos[slot] = len(seq) - 1
        logits, cache = M.serve_decode(params, cfg, jnp.asarray(tok),
                                       jnp.asarray(pos), cache)
        got.append(np.asarray(logits[slot, 0]))
    w = FAM.canonical(sz, words, jnp.float32)
    ids = np.zeros(FAM.Q_BLOCK, np.int32)
    ids[:len(seq)] = seq
    want = len(prompt) - 1 + np.arange(len(got))
    ref = np.asarray(FAM.logits_at(sz, w, jnp.asarray(ids),
                                   jnp.asarray(want, np.int32), "f32"))
    np.testing.assert_allclose(np.stack(got)[:, :sz.vocab], ref, rtol=0,
                               atol=2e-4)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: every share draws the same router,
    shared expert and experts as the whole model, and the program's
    expert layers of the four shares, with the shared expert counted
    once, add up to the reference's uncut layer (all 8 experts)."""
    from repro.models import moe
    whole = _f32(tiny_mla.small_model(n_routed_experts=8,
                                      routed_experts_offset=0))
    sz_all = FAM.sizes(whole)
    words = weights.seed_words(2**33 + 21)
    w_all = FAM.canonical(sz_all, words, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, sz_all.d_model))
    layer = 1                                   # first expert layer: 0
    ref = FAM.experts_ref(sz_all, {n: w_all[n][layer - 1] for n in (
        "router", "s_gate", "s_up", "s_down") + FAM.EXPERT_BANKS},
        x.reshape(-1, sz_all.d_model))
    total = 0.0
    for rank in range(4):
        share = _f32(tiny_mla.small_model(n_routed_experts=2,
                                          routed_experts_offset=2 * rank))
        cfg, sz = FAM.served_config(share), FAM.sizes(share)
        w = FAM.canonical(sz, words, jnp.float32)
        for n in w:
            want = w_all[n][:, 2 * rank:2 * rank + 2] \
                if n in FAM.EXPERT_BANKS else w_all[n]
            np.testing.assert_array_equal(np.asarray(w[n]), np.asarray(want))
        p = FAM.program_params(cfg, sz, words)
        y, picks = moe.moe_dropless(
            jax.tree.map(lambda a: a[layer - 1], p["layers"]["moe"]), cfg, x)
        total = total + y
        if rank:          # the shared expert counts once
            from repro.models import layers
            total = total - layers.mlp(
                jax.tree.map(lambda a: a[layer - 1],
                             p["layers"]["moe"]["shared"]), cfg, x)
    np.testing.assert_allclose(np.asarray(total).reshape(ref.shape),
                               np.asarray(ref), rtol=0, atol=2e-5)


def _cell(root, model=None):
    model = model or tiny_mla.small_model()
    tiny.write(root, [("tiny.mla", "tiny-mla", model, "emix",
                       tiny.ENGINE_MIX, tiny.ENGINE_CELL)],
               metrics=("expert_load_max_over_mean", "mla_roofline.decode",
                        "moe_roofline.decode"))
    return registry.load_cell("tiny.mla", root, root / "chipbench")


@pytest.fixture
def fresh_steps():
    from repro.serving import engine
    engine._jit_steps.cache_clear()
    yield
    engine._jit_steps.cache_clear()


def test_tiny_engine_cell_is_correct_and_reads_its_expert_load(
        tmp_path, fresh_steps):
    """A traced run of a tiny cell of the family on the CPU: correct, the
    expert load read from the engine's counters, and the two rooflines,
    which need a device trace, left out without an error."""
    dev, peaks = tiny.cpu_device()
    out = R.run_cell(_cell(tmp_path), 2**31 + 9, 2.0, True, dev, peaks)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0
    assert "mla_roofline.decode" not in out["metrics"]
    assert "moe_roofline.decode" not in out["metrics"]


def test_altered_token_in_a_tiny_cell_is_not_correct(tmp_path, fresh_steps,
                                                     monkeypatch):
    from repro.serving import engine
    good = engine._device_sample
    monkeypatch.setattr(engine, "_device_sample",
                        lambda logits, key, temps:
                        (good(logits, key, temps) + 1) % logits.shape[-1])
    dev, peaks = tiny.cpu_device()
    out = R.run_cell(_cell(tmp_path), 2**31 + 9, 2.0, False, dev, peaks)
    assert out["correct"] is False


# ---- the readers, on hand-made traces and counters -----------------------

def _readings(model, counters, ops, n_exec=2, trace=(1.0, 2.0), fam=FAM):
    """Two requests decoding in the traced second (as in the mfu test):
    request a's tokens 1 and 2 attend 11 and 12 lines, b's token 1
    attends 31; two decode steps."""
    a = Tracked(NS(prompt_ids=[1] * 10, max_new_tokens=8),
                times=[0.5, 1.2, 1.4, 2.5])
    b = Tracked(NS(prompt_ids=[1] * 30, max_new_tokens=8), times=[1.2, 1.4])
    rec = Recorder()
    log = EngineLog("engine", engine=None)
    log.tracked = [a, b]
    rec.logs.append(log)
    red = NS(program_time=lambda p: (0.004, n_exec)
             if p == "jit_decode_fn" else (0.0, 0), ops=ops)
    return e2e.Readings(
        cell=NS(family=fam, model=model,
                cell={"deployment": {"slots": 32, "max_len": 4096}}),
        result={"window": (0, 3), "recorder": rec, "sizes": fam.sizes(model),
                "counters": {"engine": counters}},
        ctx=NS(trace_host=trace), t_start=0.0,
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}, reduced=red)


def test_rooflines_read_scoped_time_against_the_bytes(monkeypatch):
    model = json.loads((registry.PKG / "configs" / "deepseek-v2-lite.json")
                       .read_text())
    sz = FAM.sizes(model)
    monkeypatch.setattr(FAM, "decode_scopes", lambda cell: {
        "jit_decode_fn/fusion.1": "mla", "jit_decode_fn/fusion.2": "mla",
        "jit_decode_fn/fusion.3": "moe"})
    ops = {"jit_decode_fn/fusion.1": 0.004, "jit_decode_fn/fusion.2": 0.002,
           "jit_decode_fn/fusion.3": 0.012, "jit_decode_fn/fusion.4": 0.5,
           "jit_prefill_fn/fusion.1": 0.7}
    c = {"steps": 40, "experts_hit": 40 * 26 * 7.5, "expert_picks_0": 30,
         "expert_picks_1": 10}
    r = _readings(model, c, ops)
    mla = registry.load_reader("mla_roofline.decode")(r)
    moe = registry.load_reader("moe_roofline.decode")(r)
    # lines 11 + 12 + 31 over two steps; 0.006 s of mla ops over 2 runs
    lines = (11 + 12 + 31) / 2
    want = (26 + 1) * (lines * 576 * 2 + FAM.attention_params(sz) * 2)
    assert mla == pytest.approx(100 * want / 819e9 / 0.003)
    assert want == pytest.approx(27 * (lines * 1152 + 13_760_000 * 2),
                                 rel=1e-3)
    moe_bytes = (26 * 7.5 * 3 * 2048 * 1408 * 2
                 + 26 * (2 * 3 * 2048 * 1408 * 2 + 2048 * 64 * 2))
    assert moe == pytest.approx(100 * moe_bytes / 819e9 / 0.006)
    assert 0 < mla < 100 and 0 < moe < 100
    assert registry.load_reader("expert_load_max_over_mean")(r) == \
        pytest.approx(1.5)
    # no trace, no decode in it, or no counter: nothing, and no error
    for bad in (dict(r.__dict__, reduced=None),
                dict(r.__dict__, ctx=NS(trace_host=(5.0, 6.0)))):
        rr = e2e.Readings(**bad)
        assert registry.load_reader("mla_roofline.decode")(rr) is None
    r.result["counters"] = {"engine": {"steps": 3}}
    assert registry.load_reader("moe_roofline.decode")(r) is None
    assert registry.load_reader("expert_load_max_over_mean")(r) is None


def test_readers_read_nothing_for_a_family_without_the_scopes():
    """Where the program has no such scope or counter, as a dense cell or
    a program before this family, the readers return nothing."""
    model = json.loads((registry.PKG / "configs" / "internlm2-1.8b.json")
                       .read_text())
    dense = registry.load_family("dense")
    r = _readings(model, {"steps": 4}, {"jit_decode_fn/fusion.1": 0.01},
                  fam=dense)
    for name in ("mla_roofline.decode", "moe_roofline.decode",
                 "expert_load_max_over_mean"):
        assert registry.load_reader(name)(r) is None


HLO = """HloModule m

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  ROOT %e = f32[4] exponential(%p), metadata={op_name="jit(f)/while/body/mla/exp"}
}

%fused_b (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  ROOT %n = f32[4] negate(%p), metadata={op_name="jit(f)/moe/neg"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4] parameter(0)
  %fusion.1 = f32[4] fusion(%x), kind=kLoop, calls=%fused_a
  %fusion.2 = f32[4] fusion(%fusion.1), kind=kLoop, calls=%fused_b, metadata={op_name="jit(f)/add"}
  %fusion.3 = f32[4] fusion(%fusion.2), kind=kLoop, calls=%fused_b
  %ragged-dot-none = f32[4] custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.5 = f32[4] fusion(%ragged-dot-none), kind=kLoop, calls=%fused_b
  %custom-call.6 = f32[4] custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="kernel"}
  %add.7 = f32[4] add(%fusion.5, %custom-call.6), metadata={op_name="jit(f)/add"}
  ROOT %dot.4 = f32[4] add(%add.7, %x), metadata={op_name="jit(f)/mla/add"}
}
"""


def test_op_scopes_from_compiled_hlo():
    """An op's own ``op_name`` decides its scope; a fusion without one
    takes its fused instructions' scope; a kernel's custom call that kept
    no scope (the TPU's ragged dot) takes its users'; an op outside both
    is left out."""
    got = FAM.scopes_of_hlo(HLO)
    assert got["fusion.1"] == "mla" and got["fusion.3"] == "moe"
    assert got["dot.4"] == "mla" and "fusion.2" not in got
    assert got["ragged-dot-none"] == "moe" and "custom-call.6" not in got
    assert "x" not in got and "add.7" not in got


def test_decode_program_carries_both_scopes():
    """The tiny cell's decode program, compiled on the CPU: ops under
    ``mla`` and under ``moe``, keyed as the trace reduction keys them."""
    model = tiny_mla.small_model()
    got = FAM.decode_scopes(NS(model=model, cell={"deployment": {
        "slots": 2, "max_len": 64}}))
    assert {"mla", "moe"} <= set(got.values())
    assert all(k.startswith("jit_decode_fn/") for k in got)


def test_decode_flops_count_the_active_work():
    """Per decode row: 2 per active weight plus the absorbed attention
    over the context; the routed experts at 6 x 8 / 64 of one."""
    model = json.loads((registry.PKG / "configs" / "deepseek-v2-lite.json")
                       .read_text())
    sz = FAM.sizes(model)
    d = FAM.decode_flops(sz, 1000) - FAM.decode_flops(sz, 0)
    assert d == 2 * 27 * 16 * (2 * 512 + 64) * 1000
    f0 = FAM.decode_flops(sz, 0)
    # 2 x the active weights (1.27 G): head 210 M, layer 0's SwiGLU 67 M,
    # 27 attentions 372 M, 26 x (shared 17.3 M, 0.75 of an 8.65 M routed
    # expert, router 0.13 M)
    assert f0 == pytest.approx(2 * 1.27e9, rel=0.01)
