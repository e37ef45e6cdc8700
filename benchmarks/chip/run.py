#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the accelerator it finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads the cell's configuration, makes its weights and traffic from the
seed, warms up every shape the traffic uses, measures an open loop for
``--seconds``, checks the served tokens against the float32 reference,
and prints one JSON object as the last line of standard output. With
``--trace 0`` its metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of part
of the window. Without a TPU, or with fewer chips than the cell asks
for, it exits 3 and prints no result.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` where
that is set, and ``<checkout>/.jax_cache`` otherwise.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[2]
for _p in (str(REPO / "src"), str(REPO)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = _args(argv)
    enable_cache()
    from benchmarks.chip import harness
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return 3
    harness.print_result(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
