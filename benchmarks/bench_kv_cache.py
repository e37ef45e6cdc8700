"""Benchmark: KV cache management (survey dim 2a/2b).

  * selector fidelity: decode-logit KL divergence of each eviction policy
    vs the full cache at matched budgets (the eviction-quality claim),
  * budget policies: pyramid/adaptive vs uniform at the same total budget,
  * paging: fragmentation waste of paged vs reserve-max allocation
    (PagedAttention's core claim), plus paged-kernel gather overhead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_jit
from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
# analysis: allow L001 (micro-bench: times internal kv-cache kernels
# directly; the facade would add dispatch overhead to the measurement)
from repro.core.kv_cache.budget import (adaptive_budgets, cake_layer_scores,
                                        pyramid_budgets, uniform_budgets)
# analysis: allow L001 (micro-bench)
from repro.core.kv_cache.paged import SeqBlocks, fragmentation_waste
# analysis: allow L001 (micro-bench)
from repro.core.kv_cache.selection import SELECTORS
from repro.models import build
from repro.models.attention import simple_sdpa


def _kl(p_logits, q_logits):
    p = jax.nn.log_softmax(p_logits, -1)
    q = jax.nn.log_softmax(q_logits, -1)
    return float(jnp.sum(jnp.exp(p) * (p - q), -1).mean())


def selector_fidelity() -> None:
    """One attention layer, long synthetic history, decode one step."""
    rng = np.random.RandomState(0)
    b, s, h, d, hq = 2, 256, 2, 16, 4
    k = jnp.asarray(rng.randn(b, s, h, d) * 0.4, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    q = jnp.asarray(rng.randn(b, 1, h, hq // h, d), jnp.float32)
    pos = jnp.arange(s)
    full = simple_sdpa(q, k, v, q_pos=jnp.asarray([[s]] * b), k_pos=pos,
                       causal=True)
    attn_hist = jax.nn.softmax(
        jnp.einsum("bqkgd,bckd->bkgqc", q, k).reshape(b, -1, 1, s) * 4.0, -1)
    for name in sorted(SELECTORS):
        for budget in (64, 32):
            k2, v2, kept = SELECTORS[name](k, v, budget=budget,
                                           attn=attn_hist)
            out = simple_sdpa(q, k2, v2, q_pos=jnp.asarray([[s]] * b),
                              k_pos=kept, causal=True)
            err = float(jnp.abs(out - full).mean() /
                        (jnp.abs(full).mean() + 1e-9))
            us = time_jit(jax.jit(
                lambda kk, vv, n=name, bu=budget: SELECTORS[n](
                    kk, vv, budget=bu, attn=attn_hist)[0]), k, v)
            emit(f"kvsel/{name}/b{budget}", us, f"rel_err={err:.4f}")


def budget_policies() -> None:
    """Same total budget, different per-layer split: attention mass kept.

    Two synthetic regimes decide the verdict on PyramidKV's premise:
      * funneled  -- deep layers concentrate mass on a few hot tokens (the
        "pyramidal information funneling" the paper observed): pyramid and
        adaptive beat uniform;
      * flat      -- mild sharpening only, no funnel: uniform is NOT beaten
        (DynamicKV's critique of static architectural heuristics).
    """
    rng = np.random.RandomState(1)
    layers, s = 8, 128

    def synth(funneled: bool):
        attns = []
        for li in range(layers):
            base = jax.nn.softmax(jnp.asarray(rng.randn(1, 2, 16, s)), -1)
            if funneled:
                # fraction of mass on 2 hot tokens (attention sinks) grows
                # to 95% with depth -- PyramidKV's measured funnel
                hot = jnp.zeros((s,)).at[
                    jnp.asarray(rng.choice(s, 2, replace=False))].set(0.5)
                w = li / (layers - 1) * 0.95
                a = (1 - w) * base + w * hot[None, None, None, :]
            else:
                sharp = 0.3 + 2.5 * li / layers
                a = jax.nn.softmax(
                    jnp.asarray(rng.randn(1, 2, 16, s)) * sharp, -1)
            attns.append(a)
        return attns

    total = layers * 24
    for regime in ("funneled", "flat"):
        attns = synth(regime == "funneled")
        schemes = {
            "uniform": uniform_budgets(total, layers, min_per_layer=1),
            "pyramid": pyramid_budgets(total, layers, min_per_layer=1),
            "adaptive": adaptive_budgets(total, cake_layer_scores(attns),
                                         min_per_layer=1),
        }
        for name, budgets in schemes.items():
            mass = 0.0
            for li, a in enumerate(attns):
                scores = np.asarray(a.sum((0, 1, 2)))
                top = np.sort(scores)[::-1][:budgets[li]]
                mass += float(top.sum() / scores.sum())
            emit(f"kvbudget/{regime}/{name}", 0.0,
                 f"attn_mass_kept={mass / layers:.4f};total={total}")


def paging() -> None:
    rng = np.random.RandomState(2)
    lengths = rng.randint(16, 900, size=64)
    max_len = 1024
    bs = 16
    seqs = [SeqBlocks(block_ids=list(range((l + bs - 1) // bs)), length=l)
            for l in lengths]
    w = fragmentation_waste(seqs, bs)
    contiguous_waste = sum(max_len - l for l in lengths)
    emit("paging/fragmentation", 0.0,
         f"paged_waste_frac={w['waste_frac']:.4f};"
         f"contig_waste_frac={contiguous_waste / (64 * max_len):.4f}")
    # paged kernel vs contiguous reference decode (structural overhead)
    from repro.kernels import ref
    b, hq, kvh, d, page, pps = 4, 8, 2, 32, 16, 8
    P = 64
    q = jnp.asarray(rng.randn(b, hq, d), jnp.float32)
    kp = jnp.asarray(rng.randn(kvh, P, page, d), jnp.float32)
    vp = jnp.asarray(rng.randn(kvh, P, page, d), jnp.float32)
    bt = jnp.asarray(rng.choice(P, (b, pps)), jnp.int32)
    sl = jnp.asarray(rng.randint(page, pps * page, b), jnp.int32)
    us_paged = time_jit(jax.jit(
        lambda *a: ref.paged_attention_ref(*a)), q, kp, vp, bt, sl)
    # [KVH, B, pps, page, D] -> contiguous [B, KVH, pps * page, D]
    k_contig = jnp.moveaxis(kp[:, bt].reshape(kvh, b, pps * page, d), 0, 1)
    v_contig = jnp.moveaxis(vp[:, bt].reshape(kvh, b, pps * page, d), 0, 1)
    us_contig = time_jit(jax.jit(
        lambda qq, kk, vv: ref.flash_attention_ref(
            qq.reshape(b, hq, 1, d), kk, vv, causal=False)),
        q, k_contig, v_contig)
    emit("paging/gather_overhead", us_paged,
         f"contiguous_us={us_contig:.1f}")


def run() -> None:
    selector_fidelity()
    budget_policies()
    paging()


if __name__ == "__main__":
    enable_compile_cache()
    run()
