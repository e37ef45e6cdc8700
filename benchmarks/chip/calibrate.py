#!/usr/bin/env python3
"""Readings that a cell's ``logit_gap`` limit is set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds <s>

For each seed, in one process, a full run of the cell (its own rate,
window and sample) followed by the float8 control on the same prompts
and served tokens. Prints one JSON line per seed with the program's
widest gap and verdict, and the control's, judged alike. Not run by the
benchmark itself.
"""
import time

import argparse  # noqa: E402
import json  # noqa: E402

import run  # noqa: E402  (sets up paths and the compile cache)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    run.enable_cache()
    import gc
    from benchmarks.chip import harness
    for seed in (int(x) for x in args.seeds.split(",")):
        gc.collect()                    # the last run's device arrays
        out = harness.run_cell(args.workload, seed, args.seconds, False,
                               t_start=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "logit_gap": out["checks"]["logit_gap"]["value"],
                          "control_correct": out["control"]["correct"],
                          "control_gap":
                              out["control"]["checks"]["logit_gap"]["value"],
                          "unfinished": out["failed"],
                          "attempted": out["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
