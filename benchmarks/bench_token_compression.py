"""Benchmark: visual token compression (survey dim 1).

Measures, per pruner:
  * wall time of the compression op itself,
  * attention-FLOPs saved at the backbone (quadratic in kept tokens),
  * QUALITY: end-to-end logit fidelity -- KL(full-model || pruned-model)
    on a smoke VLM -- plus oracle-attention recall of the kept set.
The survey's core claim: large visual-token reductions cost little output
fidelity because visual tokens are redundant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_jit
from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
# analysis: allow L001 (micro-bench: times internal pruning kernels
# directly rather than through the per-request facade strategies)
from repro.core.token_compression.pruning import PRUNERS
from repro.models import build


def _kl(p_logits, q_logits):
    p = jax.nn.log_softmax(p_logits, -1)
    q = jax.nn.log_softmax(q_logits, -1)
    return float(jnp.sum(jnp.exp(p) * (p - q), -1).mean())


def run() -> None:
    cfg = get_config("qwen2-vl-2b", smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    b, s, nv, d = 2, 24, cfg.num_visual_tokens, cfg.d_model

    # structured "image": few distinct textures + noise (redundancy source)
    centers = rng.randn(4, d) * 0.5
    ve = np.stack([centers[rng.randint(4, size=nv)]
                   + 0.05 * rng.randn(nv, d) for _ in range(b)])
    batch = {
        "tokens": jnp.asarray(rng.randint(1, cfg.vocab_size, (b, s))),
        "visual_embeds": jnp.asarray(ve, jnp.float32),
    }
    full_logits, _ = jax.jit(model.forward)(params, batch)
    full_last = full_logits[:, -1]

    fwd = jax.jit(model.forward)
    for name in sorted(PRUNERS):
        for keep_ratio in (0.5, 0.25):
            keep = max(1, int(nv * keep_ratio))
            kwargs = {}
            if name == "fastv":
                kwargs["scores"] = jnp.asarray(rng.rand(b, nv), jnp.float32)
            if name in ("sparsevlm", "cdpruner"):
                emb = jax.jit(lambda p, t: p["embed"]["tok"][t])(
                    params, batch["tokens"])
                kwargs["query"] = emb
            fn = jax.jit(lambda e, kw=kwargs, n=name, k=keep:
                         PRUNERS[n](e, k, **kw)[0])
            us = time_jit(fn, batch["visual_embeds"])
            kept = fn(batch["visual_embeds"])
            pruned_logits, _ = fwd(params, dict(batch, visual_embeds=kept))
            kl = _kl(full_last, pruned_logits[:, -1])
            # attention FLOPs ~ (Nv+S)^2: report the quadratic saving
            frac = ((keep + s) ** 2) / ((nv + s) ** 2)
            emit(f"tokcomp/{name}/keep{keep_ratio}", us,
                 f"kl={kl:.4f};attn_flops_frac={frac:.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
