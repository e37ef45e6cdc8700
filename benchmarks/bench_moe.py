"""Benchmark: sparse-MoE routing balance (survey dim 3b + §V open problem).

The survey's §V: "the routing algorithm in MoE often routes visual context
to a small subset of 'popular' experts ... the model stops functioning like
a true mixture of experts." The Switch/GShard load-balance auxiliary loss
is the surveyed mitigation. This harness trains a small MoE with and
without the aux loss and reports expert-load entropy + drop rates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config
from repro.launch.cache import enable_compile_cache
from repro.models import build
from repro.models.moe import apply_moe
from repro.training import (OptimizerConfig, SyntheticDataConfig,
                            adamw_init, adamw_update)
from repro.training.data import make_batch


def run() -> None:
    # (a) mechanism check: the Switch lb_loss signal must separate a
    # collapsed routing from a balanced one by a wide margin
    e, t = 8, 512
    logits_bal = jnp.zeros((t, e))
    logits_col = jnp.zeros((t, e)).at[:, 0].set(8.0)
    for name, lg in (("balanced", logits_bal), ("collapsed", logits_col)):
        probs = jax.nn.softmax(lg, -1)
        _, idx = jax.lax.top_k(probs, 2)
        one_hot = jax.nn.one_hot(idx, e)
        load = one_hot.sum((0, 1)) / (t * 2)
        lb = float(e * jnp.sum(load * probs.mean(0)))
        emit(f"moe/lb_loss_signal/{name}", 0.0, f"lb_loss={lb:.3f}"
             ";(1.0=perfectly balanced)")

    # (b) training path: smoke-scale MoE stays balanced either way (real
    # collapse needs long training runs); rows prove the aux pathway runs
    base = get_config("arctic-480b", smoke=True).with_(vocab_size=256)
    for coef, tag in ((0.0, "no_aux"), (5e-2, "aux")):
        cfg = base.with_(router_aux_loss_coef=coef)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        oc = OptimizerConfig(lr=2e-3, warmup_steps=3, total_steps=40,
                             weight_decay=0.0)
        opt = adamw_init(params)

        @jax.jit
        def step(params, opt, batch):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: model.loss(p, batch), has_aux=True)(params)
            params, opt, _ = adamw_update(oc, grads, opt, params)
            return params, opt, loss

        dc = SyntheticDataConfig(batch=4, seq_len=24)
        for s in range(40):
            batch = {k: jnp.asarray(v)
                     for k, v in make_batch(cfg, dc, s).items()}
            params, opt, loss = step(params, opt, batch)

        # measure routing balance on held-out data through layer-0 MoE
        batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, dc, 99).items()}
        emb = params["embed"]["tok"][batch["tokens"]]
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        _, aux = apply_moe(lp["moe"], emb, cfg)
        load = np.asarray(aux["load"])
        load = load / load.sum()
        ent = -(load * np.log(load + 1e-9)).sum() / np.log(len(load))
        emit(f"moe/balance/{tag}", 0.0,
             f"load_entropy={ent:.4f};max_load={load.max():.3f};"
             f"dropped={float(aux['dropped_frac']):.3f};"
             f"final_loss={float(loss):.3f}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
