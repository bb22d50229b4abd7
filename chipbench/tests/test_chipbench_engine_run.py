"""A whole run of the engine entry on the CPU at a tiny size: one engine
serves a burst and then an open-loop Poisson trace. A sound run is
correct; each fault planted in the timed path (a token altered where it
is produced, a decode step that leaves the KV cache as it was, attention
over half of each row's context) and the control precision are not.
Requests still queued when the window closes are neither attempted nor
failed."""
from __future__ import annotations

import json

import pytest

from chipbench import registry
from chipbench import run as R
from chipbench.tests import tiny


def _cell(root, mix=tiny.ENGINE_MIX, cell=tiny.ENGINE_CELL):
    model = tiny.small_model("internlm2-1.8b")
    tiny.write(root, [("tiny.engine", "tiny-internlm2", model, "emix", mix,
                       cell)])
    return registry.load_cell("tiny.engine", root, root / "chipbench")


@pytest.fixture
def fresh_steps():
    from repro.serving import engine
    engine._jit_steps.cache_clear()
    yield
    engine._jit_steps.cache_clear()


def _run(cell, **kw):
    dev, peaks = tiny.cpu_device()
    return R.run_cell(cell, 2**31 + 9, 2.0, False, dev, peaks, **kw)


def _info(err: str) -> dict:
    line = [x for x in err.splitlines() if x.startswith("chipbench: info ")]
    return json.loads(line[-1][len("chipbench: info "):])


def test_sound_engine_run_is_correct(tmp_path, fresh_steps):
    out = _run(_cell(tmp_path))
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p99_ms", "tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert out["check"]["max_logit_gap"]["limit"] == 0.02


def _alter_token(monkeypatch):
    from repro.serving import engine
    good = engine._device_sample
    monkeypatch.setattr(engine, "_device_sample",
                        lambda logits, key, temps:
                        (good(logits, key, temps) + 1) % logits.shape[-1])


def _keep_cache(monkeypatch):
    from repro.models import kvcache
    monkeypatch.setattr(kvcache, "write_decode",
                        lambda ck, cv, k, v, pos, window: (ck, cv))


def _half_context(monkeypatch):
    from repro.models import layers
    good = layers.decode_attention
    monkeypatch.setattr(layers, "decode_attention",
                        lambda q, k, v, *, kv_len:
                        good(q, k, v, kv_len=(kv_len + 1) // 2))


@pytest.mark.parametrize("fault", [_alter_token, _keep_cache, _half_context],
                         ids=["token_altered", "cache_unchanged",
                              "half_context"])
def test_fault_in_the_timed_path_is_not_correct(tmp_path, fresh_steps,
                                                monkeypatch, fault):
    fault(monkeypatch)
    assert _run(_cell(tmp_path))["correct"] is False


def test_control_precision_is_judged_not_correct(tmp_path, fresh_steps):
    out = _run(_cell(tmp_path), controls=("fp8",))
    assert out["correct"] is True
    ctl = out["controls"]["fp8"]
    assert ctl["correct"] is False, ctl
    assert ctl["max_logit_gap"] > out["check"]["max_logit_gap"]["limit"]


def test_requests_queued_at_the_close_are_neither_attempted_nor_failed(
        tmp_path, fresh_steps, capfd):
    """Far above what two slots serve, most requests still wait when the
    window closes: they are withdrawn and logged, the admitted ones all
    finish, and the run is correct."""
    mix = dict(tiny.ENGINE_MIX, rate=60.0, burst=6,
               answer=dict(tiny.ENGINE_MIX["answer"], min=24))
    cell = dict(tiny.ENGINE_CELL,
                deployment=dict(tiny.ENGINE_CELL["deployment"], slots=2))
    out = _run(_cell(tmp_path, mix, cell))
    detail = _info(capfd.readouterr().err)["detail"]
    assert out["correct"] is True, out["check"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert detail["queued_at_close"] > 0
    assert detail["submitted"] == out["attempted"] + detail["queued_at_close"]
    assert detail["finished"] == out["attempted"]
