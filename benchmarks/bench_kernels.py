"""Benchmark: hardware-aware attention kernels (survey dim 3c).

The Pallas kernels run through ``repro.kernels.ops``, which compiles them
natively on a TPU and runs them in the Pallas interpreter anywhere else
(orders of magnitude slower -- correctness-grade timing only). Every row
names the backend it actually ran on (``xla_cpu``, ``xla_tpu``,
``pallas_interpret``, ``pallas_tpu``) and the document records the
device, so an interpreter timing is never read as a kernel timing.

``--emit-bench BENCH_kernels.json`` writes the schema-v1 per-kernel
rows (min/mean/std us per call, warmup-correct -- see
``benchmarks.common.time_jit``) that ``python -m repro.obs.regress``
gates CI against.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timing, emit, time_jit
from repro.kernels import ref
from repro.kernels.ops import flash_attention, interpret_mode, paged_attention
from repro.launch.cache import enable_compile_cache
from repro.models.attention import blockwise_sdpa


def _naive(q, k, v, pos):
    s = jnp.einsum("bqkgd,bckd->bkgqc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (q.shape[-1] ** 0.5)
    mask = pos[None, :] <= pos[:, None]
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgqc,bckd->bkgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1)


def _xla_backend() -> str:
    return f"xla_{jax.default_backend()}"


def _pallas_backend() -> str:
    return "pallas_interpret" if interpret_mode() else "pallas_tpu"


def _row(rows, kernel: str, backend: str, shape: str, t: Timing,
         iters: int, derived: str = "") -> None:
    rows.append({"kernel": kernel, "backend": backend, "shape": shape,
                 "us_per_call": t.stats(), "iters": iters})
    emit(f"kern/{kernel}/{shape}", t, derived)


def bench_blockwise(rows) -> None:
    """XLA blockwise flash-style path vs naive materialized attention."""
    rng = np.random.RandomState(0)
    for s in (512, 2048):
        b, kvh, g, d = 1, 2, 2, 64
        q = jnp.asarray(rng.randn(b, s, kvh, g, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
        pos = jnp.arange(s)
        t_naive = time_jit(jax.jit(lambda *a: _naive(*a, pos)), q, k, v,
                           iters=3)
        t_block = time_jit(jax.jit(
            lambda qq, kk, vv: blockwise_sdpa(qq, kk, vv, q_pos=pos,
                                              k_pos=pos, causal=True,
                                              block_k=512)), q, k, v,
            iters=3)
        shape = f"b{b}_kvh{kvh}_g{g}_s{s}_d{d}"
        _row(rows, "blockwise_sdpa", _xla_backend(), shape, t_block, 3,
             f"naive_us={t_naive:.0f}")
        _row(rows, "naive_sdpa", _xla_backend(), shape, t_naive, 3)


def bench_flash(rows) -> None:
    """Pallas flash-attention prefill kernel."""
    rng = np.random.RandomState(1)
    b, h, kvh, d = 1, 4, 2, 32
    for s in (64, 128):
        q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, kvh, s, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, kvh, s, d), jnp.float32)
        t = time_jit(lambda: flash_attention(q, k, v, causal=True,
                                             block_q=32, block_k=32),
                     iters=3)
        _row(rows, "flash_attention", _pallas_backend(),
             f"b{b}_h{h}_s{s}_d{d}", t, 3)


def bench_paged(rows) -> None:
    """Pallas paged decode-attention kernel (head-major pages)."""
    rng = np.random.RandomState(2)
    b, h, kvh, d, page = 2, 4, 2, 32, 16
    for pps in (4, 8):                 # pages per sequence
        P = b * pps
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        kp = jnp.asarray(rng.randn(kvh, P, page, d), jnp.float32)
        vp = jnp.asarray(rng.randn(kvh, P, page, d), jnp.float32)
        bt = jnp.asarray(rng.choice(P, (b, pps), replace=False),
                         jnp.int32)
        sl = jnp.asarray(rng.randint(page, pps * page, b), jnp.int32)
        t = time_jit(lambda: paged_attention(q, kp, vp, bt, sl), iters=3)
        _row(rows, "paged_attention", _pallas_backend(),
             f"b{b}_h{h}_ctx{pps * page}_d{d}", t, 3)


def check_flash_vs_ref(rows) -> None:
    """Correctness spot check against the oracle on the backend the
    kernel ran on; ``max_err`` is informational to the regress gate."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 4, 64, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    err = float(jnp.abs(out - expect).max())
    rows.append({"kernel": "flash_attention", "check": "allclose_vs_ref",
                 "backend": _pallas_backend(), "max_err": err})
    emit(f"kern/{_pallas_backend()}_allclose", 0.0, f"max_err={err:.2e}")


def run(emit_bench: str = None) -> None:
    rows = []
    bench_blockwise(rows)
    bench_flash(rows)
    bench_paged(rows)
    check_flash_vs_ref(rows)
    if emit_bench:
        dev = jax.devices()[0]
        doc = {"schema_version": 1, "bench": "kernels",
               "device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(jax.devices())},
               "backend_note": "each row's backend is the one it ran on; "
                               "pallas_interpret rows are correctness-"
                               "grade (recapture baselines per runtime)",
               "rows": rows}
        with open(emit_bench, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {emit_bench} ({len(rows)} rows)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-bench", metavar="PATH",
                    help="write schema-v1 per-kernel timing rows "
                         "(BENCH_kernels.json) for repro.obs.regress")
    args = ap.parse_args(argv)
    enable_compile_cache()
    run(emit_bench=args.emit_bench)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
