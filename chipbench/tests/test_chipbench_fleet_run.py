"""A whole run of the fleet entry on the CPU at a tiny size: the edge
engine, the cloud pool, the router and the scheduler serve an open-loop
trace of DAG queries. A sound run is correct; a token altered where it
is produced, on either engine, is not."""
from __future__ import annotations

import pytest

from chipbench import registry
from chipbench import run as R
from chipbench.tests import tiny


@pytest.fixture
def fleet_cell(tmp_path):
    model = tiny.small_model("qwen2-1.5b")
    tiny.write(tmp_path, [("tiny.fleet", "tiny-qwen2", model, "fmix",
                           tiny.FLEET_MIX, tiny.FLEET_CELL)])
    return registry.load_cell("tiny.fleet", tmp_path, tmp_path / "chipbench")


@pytest.fixture
def fresh_steps():
    from repro.serving import engine
    engine._jit_steps.cache_clear()
    yield
    engine._jit_steps.cache_clear()


def _run(cell, **kw):
    dev, peaks = tiny.cpu_device()
    return R.run_cell(cell, 2**31 + 5, 3.0, False, dev, peaks, **kw)


def test_sound_fleet_run_is_correct(fleet_cell, fresh_steps):
    out = _run(fleet_cell)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["itl_p99_ms"]["value"] > 0


def test_token_altered_where_produced_is_not_correct(fleet_cell, fresh_steps,
                                                     monkeypatch):
    from repro.serving import engine
    good = engine._device_sample

    def altered(logits, key, temps):
        return (good(logits, key, temps) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_device_sample", altered)
    assert _run(fleet_cell)["correct"] is False


def test_decode_step_leaving_the_cache_unchanged_is_not_correct(
        fleet_cell, fresh_steps, monkeypatch):
    from repro.models import kvcache
    monkeypatch.setattr(kvcache, "write_decode",
                        lambda ck, cv, k, v, pos, window: (ck, cv))
    assert _run(fleet_cell)["correct"] is False


def test_control_precision_is_judged_not_correct(fleet_cell, fresh_steps):
    """The control: the reference itself with float8 weights, put in the
    program's place, fails the same check that a sound run passes."""
    out = _run(fleet_cell, controls=("fp8",))
    assert out["correct"] is True
    ctl = out["controls"]["fp8"]
    assert ctl["correct"] is False, ctl
    assert ctl["max_logit_gap"] > out["check"]["max_logit_gap"]["limit"]


def test_queries_unfinished_at_the_drain_limit_are_failed(tmp_path,
                                                          fresh_steps):
    """With no time to drain, queries due late in the window are still
    running when the limit comes: the run still prints its result line,
    counts them in ``failed`` and is not correct."""
    model = tiny.small_model("qwen2-1.5b")
    mix = dict(tiny.FLEET_MIX, drain_s=0.0)
    tiny.write(tmp_path, [("tiny.fleet", "tiny-qwen2", model, "fmix", mix,
                           tiny.FLEET_CELL)])
    out = _run(registry.load_cell("tiny.fleet", tmp_path,
                                  tmp_path / "chipbench"))
    assert out["correct"] is False
    assert 0 < out["failed"] <= out["attempted"]
    assert "setup_s" in out["metrics"]
