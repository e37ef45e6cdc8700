"""Sharded execution correctness: run REAL computations on a small fake
device mesh in a subprocess (the 512-device override must never leak into
this process) and check they match single-device results."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build
    from repro.sharding.specs import (ShardingRules, param_shardings,
                                      cache_shardings)

    cfg = get_config("phi4-mini-3.8b", smoke=True).with_(dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0,
                                cfg.vocab_size)

    # single device reference
    ref_logits, ref_cache = jax.jit(
        lambda p, t: model.prefill(p, {"tokens": t}, cache_len=16))(
        params, tokens)
    dec_ref, _ = jax.jit(model.decode_step)(
        params, ref_cache, tokens[:, -1:] * 0 + 7, 12)

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = ShardingRules(mesh, fsdp=True)
    psh = param_shardings(rules, model.param_specs())
    sp = jax.device_put(params, psh)
    with mesh:
        logits, cache = jax.jit(
            lambda p, t: model.prefill(p, {"tokens": t}, cache_len=16))(
            sp, tokens)
        dec_ws, _ = jax.jit(
            lambda p, c, t: model.decode_step(p, c, t, 12,
                                              weight_stationary=True))(
            sp, cache, tokens[:, -1:] * 0 + 7)
        dec_plain, _ = jax.jit(
            lambda p, c, t: model.decode_step(p, c, t, 12))(
            sp, cache, tokens[:, -1:] * 0 + 7)

    out = {
        "prefill_err": float(jnp.abs(logits - ref_logits).max()),
        "decode_ws_err": float(jnp.abs(dec_ws - dec_ref).max()),
        "decode_plain_err": float(jnp.abs(dec_plain - dec_ref).max()),
        "ref_scale": float(jnp.abs(ref_logits).max()),
    }
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_matches_single_device():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    out = json.loads(line[0][7:])
    tol = 1e-3 * max(out["ref_scale"], 1.0)
    assert out["prefill_err"] < tol, out
    assert out["decode_plain_err"] < tol, out
    # weight-stationary decode is a LAYOUT change only: results identical
    assert out["decode_ws_err"] < tol, out
