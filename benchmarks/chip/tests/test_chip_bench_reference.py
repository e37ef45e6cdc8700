"""The float32 reference against the engine's own programs, on the CPU.

At the smoke sizes (float32 weights), the engine's jitted prefill and
its cached decode steps must give the reference's logits at every served
position, for the vision-language path (visual rows before the text)
and the text path alike; and the float8 control must not.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip_bench_smoke import SMOKE_MODELS
from benchmarks.chip import harness, reference, weights


def _engine_logits(model, params, tokens, visual, served, cache_len):
    """Prefill, then one cached decode step per served token but the
    last, through the programs the engine jits."""
    batch = {"tokens": jnp.asarray([tokens], jnp.int32)}
    if visual is not None:
        batch["visual_embeds"] = jnp.asarray(visual)[None]
    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=cache_len,
                                                 windowed=False))
    decode = jax.jit(partial(model.decode_step, windowed=False))
    logits, cache = prefill(params, batch)
    out = [logits[0, -1]]
    pos = (0 if visual is None else len(visual)) + len(tokens)
    for tok in served[:-1]:
        lg, cache = decode(params, cache, jnp.asarray([[tok]], jnp.int32),
                           jnp.asarray([pos], jnp.int32))
        out.append(lg[0])
        pos += 1
    return np.stack([np.asarray(x) for x in out])


@pytest.mark.parametrize("name", sorted(SMOKE_MODELS))
def test_engine_prefill_and_cached_decode_match_the_reference(name):
    conf = SMOKE_MODELS[name]
    m = harness.served_model(conf)
    from repro.models.registry import build
    model = build(harness.program_config(conf))
    params = weights.make(m, "float32", 7)
    assert weights.same_layout(params, jax.eval_shape(
        model.init, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, m["vocab_size"], 11).tolist()
    served = rng.integers(0, m["vocab_size"], 6).tolist()
    nv = m.get("visual_tokens", 0)
    visual = (rng.standard_normal((nv, m["hidden_size"])).astype(np.float32)
              if nv else None)
    cache_len = conf["engine"]["cache_len"]
    with jax.default_matmul_precision("highest"):
        got = _engine_logits(model, params, tokens, visual, served,
                             cache_len)
    ref = np.asarray(reference.served_logits(
        m, params, tokens, visual, served, cache_len, 8))[: len(served)]
    assert np.abs(got - ref).max() < 1e-4 * max(1.0, np.abs(ref).max())
    ctl = np.asarray(reference.served_logits(
        m, params, tokens, visual, served, cache_len, 8,
        quant="fp8"))[: len(served)]
    assert np.abs(ctl - ref).max() > 100 * np.abs(got - ref).max()


def test_select_visual_keeps_lowest_norms_in_order():
    raw = np.zeros((6, 2), np.float32)
    raw[:, 0] = [5.0, 1.0, 4.0, 2.0, 6.0, 3.0]
    kept = reference.select_visual(raw, 3)
    assert kept[:, 0].tolist() == [1.0, 2.0, 3.0]
    assert reference.select_visual(raw, None) is raw


def test_the_reference_takes_the_served_values_over_the_published():
    conf = SMOKE_MODELS["phi-smoke"]
    m = harness.served_model(conf)
    assert conf["model"]["rms_norm_eps"] == 1e-05
    assert (m["rms_norm_eps"], m["partial_rotary_factor"],
            m["rope_scaling"]) == (1e-06, 1.0, None)
    params = weights.make(conf["model"], "float32", 7)
    # the published LongRoPE is not in the reference: refused, not ignored
    with pytest.raises(ValueError, match="longrope"):
        reference.served_logits(conf["model"], params, [1, 2, 3], None,
                                [4, 5], 16, 4)


def test_partial_rotary_leaves_the_rest_of_the_head_alone():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 2, 16)),
                    jnp.float32)
    pos = jnp.arange(5)
    part = np.asarray(reference._rope(x, pos, 10000.0, 0.75))
    whole = np.asarray(reference._rope(x, pos, 10000.0, 1.0))
    assert np.array_equal(part[..., 12:], np.asarray(x)[..., 12:])
    assert not np.allclose(part[1:, :, :12], np.asarray(x)[1:, :, :12])
    assert not np.allclose(part, whole)
