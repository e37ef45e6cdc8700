"""Whole runs of a cell on the CPU, past the harness's look for a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from chip_bench_smoke import CHIP, REPO, SMOKE_LIMIT, TPU_TRACE, make_tree
from benchmarks.chip import harness, trace_reduce


def _run(tmp_path, cell, trace=False, **kw):
    root = make_tree(tmp_path)
    return harness.run_cell(cell, 2 ** 31 + 11, 2.0, trace,
                            t_start=time.perf_counter(), repo=tmp_path,
                            root=root, require_chip=False, **kw)


@pytest.mark.parametrize("cell", ["smoke.image_fastv", "smoke.text"])
def test_a_sound_run_is_correct(tmp_path, cell):
    out = _run(tmp_path, cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p50_ms",
                                   "itl_p95_ms", "output_tokens_per_s",
                                   "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["logit_gap"]["value"] <= SMOKE_LIMIT
    assert list(out)[-1] == "checks"


def test_a_traced_run_reports_per_layer_metrics(tmp_path, monkeypatch):
    # a CPU writes no device plane: the device side is the trace recorded
    # on a TPU, the rest of the run is the CPU's own
    recorded = trace_reduce.load(str(TPU_TRACE))
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    out = _run(tmp_path, "smoke.image_fastv", trace=True)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert got["compiles_in_window"]["value"] == 0
    assert got["compress_call_ms"]["value"] > 0
    assert got["decode_batch_mean"]["value"] >= 1
    assert 0 <= got["device_idle_share"]["value"] <= 100
    # shares of a peak need a known chip: silent on the CPU, never 0
    assert not {"mfu", "prefill_roofline", "decode_roofline"} & set(got)
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_no_chip_is_refused_before_any_work(tmp_path):
    root = make_tree(tmp_path)
    with pytest.raises(harness.NoChip):
        harness.run_cell("smoke.text", 1, 1.0, False, t_start=0.0,
                         repo=tmp_path, root=root)


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_the_command_without_a_tpu_exits_nonzero_and_prints_nothing():
    p = _cli(REPO, "--workload", "qwen2vl.image_chat", "--seed",
             "3000000000", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_the_command_needs_the_program_beside_it(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, "--workload", "qwen2vl.image_chat", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
