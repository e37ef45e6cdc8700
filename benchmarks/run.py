"""Benchmark harness: one module per survey taxonomy category.

    PYTHONPATH=src python -m benchmarks.run [category ...]

Rows print as ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import sys
import time

from benchmarks import (bench_decoding, bench_kernels, bench_kv_cache,
                        bench_moe, bench_serving, bench_token_compression)
from repro.launch.cache import enable_compile_cache

CATEGORIES = {
    "token_compression": bench_token_compression.run,   # survey dim 1
    "kv_cache": bench_kv_cache.run,                     # survey dim 2a/2b
    "serving": bench_serving.run,                       # survey dim 2c
    "kernels": bench_kernels.run,                       # survey dim 3c
    "moe": bench_moe.run,                               # survey dim 3b + §V
    "decoding": bench_decoding.run,                     # survey dim 4
}


def main() -> None:
    picks = sys.argv[1:] or list(CATEGORIES)
    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in picks:
        if name not in CATEGORIES:
            raise SystemExit(f"unknown category {name!r}; "
                             f"known: {sorted(CATEGORIES)}")
        CATEGORIES[name]()
    print(f"# total {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
