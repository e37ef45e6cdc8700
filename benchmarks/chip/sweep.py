#!/usr/bin/env python3
"""Find a cell's knee: one process, one window per offered rate.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 2,4,6

For each rate (requests a second) it runs the cell's window at that rate
and prints one JSON line: the end-to-end metrics, and the backlog (due
requests still without a first token) at mid-window and at the close.
The knee is the highest rate whose backlog at the close does not exceed
that at mid-window. Not run by the benchmark itself.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

import run  # noqa: E402  (sets up paths and the compile cache)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    run.enable_cache()
    import gc
    from benchmarks.chip import harness
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        t = time.perf_counter()
        gc.collect()                    # the last run's device arrays
        out = harness.run_cell(args.workload, args.seed + i, args.seconds,
                               False, t_start=t, rate=rate)
        print(json.dumps({"rate": rate, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "backlog": out["backlog"],
                          "metrics": {k: v["value"] for k, v
                                      in out["metrics"].items()},
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
