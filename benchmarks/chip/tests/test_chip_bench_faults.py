"""The check that decides ``correct`` fails what it should.

Each fault is planted under a whole run on the CPU (past the harness's
look for a chip): a served token altered where it is sampled, a decode
step that hands back its cache unchanged, and a decode step that drops
the inputs of half its batch. Each must make ``correct`` false. So must
the float8 control's tokens, judged on the same prompts by the same
verdict.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from chip_bench_smoke import SMOKE_LIMIT, make_tree
from benchmarks.chip import harness


def _run(tmp_path, **kw):
    root = make_tree(tmp_path)
    return harness.run_cell("smoke.image_fastv", 2 ** 31 + 12, 2.0, False,
                            t_start=time.perf_counter(), repo=tmp_path,
                            root=root, require_chip=False, **kw)


def _altered_token(monkeypatch):
    # analysis: allow L001 (the fault is planted where the engine samples)
    from repro.core.serving import engine
    orig = engine.sample_token

    def sample(*a, **kw):
        return (orig(*a, **kw) + 1) % 512
    monkeypatch.setattr(engine, "sample_token", sample)


def _state_unchanged(monkeypatch):
    from repro.models.transformer import Model
    orig = Model.decode_step

    def decode_step(self, params, cache, tokens, pos, **kw):
        logits, _ = orig(self, params, cache, tokens, pos, **kw)
        return logits, cache
    monkeypatch.setattr(Model, "decode_step", decode_step)


def _half_batch(monkeypatch):
    from repro.models.transformer import Model
    orig = Model.decode_step

    def decode_step(self, params, cache, tokens, pos, **kw):
        # the engine fills the lowest free slots first, so the rows a
        # light load uses are the first half
        half = tokens.shape[0] // 2
        tokens = tokens.at[:half].set(jnp.zeros_like(tokens[:half]))
        return orig(self, params, cache, tokens, pos, **kw)
    monkeypatch.setattr(Model, "decode_step", decode_step)


@pytest.mark.parametrize("plant", [_altered_token, _state_unchanged,
                                   _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_a_planted_fault_makes_the_run_incorrect(tmp_path, monkeypatch,
                                                 plant):
    plant(monkeypatch)
    out = _run(tmp_path)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_the_float8_control_fails_the_limit(tmp_path):
    out = _run(tmp_path, control=True)
    assert out["correct"]
    # the control's tokens go through the verdict the program's go through
    assert out["control"]["correct"] is False
    assert out["control"]["checks"]["logit_gap"]["value"] > 3 * SMOKE_LIMIT
