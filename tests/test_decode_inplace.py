"""The decode step writes the KV pool in place.

``Model.decode_step`` carries the stacked cache through its layer scan and
writes one row per request into it. Its logits and returned cache must be
those of a plain loop over layers that slices each layer's cache out and
runs ``decode_attention`` on it, for every cache kind the scan carries. The
engine donates the pool to the decode program, so the pool it held before a
decode is gone after it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import LVLM
from repro.configs import get_config
from repro.core.serving import Engine, EngineConfig, Request, State
from repro.models import attention as attn
from repro.models import build
from repro.models import layers as L
from repro.models import moe as MOE

B, CACHE_LEN, STEPS = 3, 96, 3


def _per_layer_decode(model, params, cache, tokens, pos, window):
    """``decode_step`` as a loop over layers, one layer's cache at a time."""
    cfg = model.cfg
    x = L.embed_tokens(params["embed"], tokens)
    cos, sin = model._cos_sin(B, pos[:, None])
    decode = attn.mla_decode_attention if cfg.use_mla \
        else attn.decode_attention
    out = dict(cache)
    for group in ("dense_layers", "layers"):
        if group not in cache:
            continue
        n = jax.tree.leaves(cache[group])[0].shape[0]
        written = []
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params[group])
            lc = jax.tree.map(lambda a: a[i], cache[group])
            h = L.apply_norm(lp["ln1"], x, cfg.norm)
            a, lc = decode(lp["attn"], h, cos, sin, cfg, lc, pos,
                           window=window)
            x = x + a
            h = L.apply_norm(lp["ln2"], x, cfg.norm)
            if "moe" in lp:
                f, _ = MOE.apply_moe(lp["moe"], h, cfg, capacity_factor=None)
            else:
                f = L.apply_mlp(lp["mlp"], h, cfg.activation)
            x = x + f
            written.append(lc)
        out[group] = jax.tree.map(lambda *a: jnp.stack(a), *written)
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    return L.unembed(params["embed"], x, cfg.logits_softcap)[:, 0], out


def _filled_cache(model, windowed, key):
    """A cache whose entries are random, so every attended slot counts."""
    cache = model.init_cache(B, CACHE_LEN, windowed=windowed)
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        a if a.dtype == jnp.int32 else jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


# every stack holds two layers or more, so a row written into the wrong
# layer shows
DEEP = {"num_layers": 4, "first_k_dense_layers": 2}
CASES = {
    "gqa": ("qwen2-vl-2b", {}, False),
    "gqa_ring": ("qwen2-vl-2b", {}, True),
    "mla_dense_prefix": ("deepseek-v3-671b", DEEP, False),
    "mla_ring": ("deepseek-v3-671b", DEEP, True),
    "moe_dense_prefix": ("arctic-480b", DEEP, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_decode_step_matches_per_layer_loop(case):
    arch, overrides, windowed = CASES[case]
    cfg = get_config(arch, smoke=True).with_(**overrides)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = _filled_cache(model, windowed, jax.random.PRNGKey(1))
    window = cfg.sliding_window if windowed else 0
    assert not windowed or "slot_pos" in cache["layers"]
    assert ("dense_layers" in cache) == bool(cfg.first_k_dense_layers)
    assert all(a.shape[0] >= 2 for a in jax.tree.leaves(cache))
    step = jax.jit(lambda p, c, t, q: model.decode_step(
        p, c, t, q, windowed=windowed))
    ref_step = jax.jit(lambda p, c, t, q: _per_layer_decode(
        model, p, c, t, q, window))
    # ragged rows: one early, one past the ring's length (it wraps), and
    # an empty row on the scratch position cache_len-1
    start = np.array([5, 70, CACHE_LEN - 1], np.int32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 1,
                              cfg.vocab_size)
    ref_cache = cache
    for t in range(STEPS):
        pos = jnp.asarray(np.where(start == CACHE_LEN - 1, start, start + t))
        logits, cache = step(params, cache, toks, pos)
        ref_logits, ref_cache = ref_step(params, ref_cache, toks, pos)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
        jax.tree.map(lambda a, r: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(r)), cache, ref_cache)
        toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


def test_engine_decode_donates_pool():
    """A decode step consumes the pool it was given; greedy tokens stay
    those ``LVLM.serve`` gives for the same seed."""
    lvlm = LVLM.from_pretrained("phi4-mini-3.8b", smoke=True, seed=3)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, lvlm.cfg.vocab_size, size=n))
               for n in (9, 14)]
    ec = EngineConfig(max_batch=2, cache_len=64)

    def requests():
        return [Request(rid=i, tokens=list(p), max_new_tokens=8)
                for i, p in enumerate(prompts)]

    eng = Engine(lvlm.model, lvlm.params, ec)
    reqs = requests()
    for r in reqs:
        eng.submit(r)
    while eng.waiting or any(r.state != State.DECODE for r in reqs):
        assert eng.step()
    held = eng.pool
    assert eng.step()                   # a decode, and nothing else
    assert all(a.is_deleted() for a in jax.tree.leaves(held))
    assert not any(a.is_deleted() for a in jax.tree.leaves(eng.pool))
    eng.run()

    served = lvlm.serve(requests(), ec)
    by_rid = {r.rid: r.generated for r in served.requests}
    assert [r.generated for r in reqs] == [by_rid[r.rid] for r in reqs]
    assert all(len(r.generated) == 8 for r in reqs)


@pytest.mark.parametrize("arch", ["qwen2-vl-2b", "phi4-mini-3.8b"])
def test_pool_kernel_decode_step_matches_xla_path(arch, monkeypatch):
    """The stacked-pool decode through the Pallas kernel (interpreted off
    a TPU) gives today's logits for every live row, finite logits for an
    inactive slot, and the same pool, which it still takes by donation."""
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = _filled_cache(model, False, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 1,
                              cfg.vocab_size)
    # two live rows, one inactive slot on the scratch position
    pos = jnp.asarray([5, 70, CACHE_LEN - 1], jnp.int32)
    lens = jnp.asarray([6, 71, 0], jnp.int32)
    xla_logits, xla_cache = jax.jit(lambda p, c, t, q: model.decode_step(
        p, c, t, q))(params, cache, toks, pos)
    monkeypatch.setattr(attn, "_pool_kernel_backend", lambda: True)
    step = jax.jit(lambda p, c, t, q, n: model.decode_step(p, c, t, q,
                                                           lens=n),
                   donate_argnums=(1,))
    held = jax.tree.map(jnp.copy, cache)
    logits, out = step(params, held, toks, pos, lens)
    assert all(a.is_deleted() for a in jax.tree.leaves(held))
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda c: model.decode_step(params, c, toks, pos, lens=lens))(cache))
    logits, xla_logits = np.asarray(logits), np.asarray(xla_logits)
    assert np.isfinite(logits).all()
    np.testing.assert_allclose(logits[:2], xla_logits[:2], atol=1e-5,
                               rtol=1e-5)

    def same_pool(a, r):
        # the inactive slot's scratch entry holds whatever its ignored
        # row computed; every other entry is today's
        a, r = np.array(a), np.array(r)
        a[:, 2, CACHE_LEN - 1] = r[:, 2, CACHE_LEN - 1] = 0
        np.testing.assert_allclose(a, r, atol=1e-5, rtol=1e-5)
    jax.tree.map(same_pool, out, xla_cache)
