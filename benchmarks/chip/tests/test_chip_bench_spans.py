"""The per-layer metrics read from the program's own profiler spans and
counters: on hand-built snapshots, and in a traced run of a cell."""
from __future__ import annotations

import time
from types import SimpleNamespace

import pytest

from chip_bench_smoke import CHIP, TPU_TRACE, make_tree
from benchmarks.chip import harness, trace_reduce

READERS = ("queue_wait_ms", "decode_rows_mean", "step_host_ms",
           "pump_host_ms")


def _site(count, total):
    return {"count": count, "wall_total_s": total, "wall_self_s": total}


def _ctx(start, end):
    return SimpleNamespace(start={"sites": start}, end={"sites": end})


def _read(name, start, end):
    return harness.load_reader(CHIP, name).read(_ctx(start, end))


def test_readers_take_the_window_s_share_of_each_span():
    start = {"queue_wait": _site(10, 1.0), "pump_host": _site(100, 0.1),
             "decode_rows": {"count": 100, "total": 1500},
             "engine_step": _site(200, 4.0), "wait:decode": _site(100, 1.0)}
    end = {"queue_wait": _site(30, 3.0), "pump_host": _site(300, 0.5),
           "decode_rows": {"count": 300, "total": 5100},
           "engine_step": _site(400, 9.0), "wait:decode": _site(300, 3.0),
           "wait:prefill": _site(20, 0.4), "wait:compress": _site(20, 0.2)}
    assert _read("queue_wait_ms", start, end) == pytest.approx(100.0)
    assert _read("pump_host_ms", start, end) == pytest.approx(2.0)
    assert _read("decode_rows_mean", start, end) == pytest.approx(18.0)
    # (5.0 s of steps - 2.0 + 0.4 + 0.2 s of waits) over 200 steps
    assert _read("step_host_ms", start, end) == pytest.approx(12.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_is_silent_without_its_span(name):
    # the parent of the program that writes these spans has none of them
    parent = {"prefill_forward": {"count": 3, "wall_total_s": 1.0,
                                  "wall_self_s": 1.0, "virtual_s": 0.1}}
    assert _read(name, {}, {}) is None
    assert _read(name, parent, parent) is None
    # present, but nothing in the window
    still = {"queue_wait": _site(5, 1.0), "pump_host": _site(5, 1.0),
             "decode_rows": {"count": 5, "total": 9},
             "engine_step": _site(5, 1.0)}
    assert _read(name, still, still) is None


def test_a_traced_run_reports_the_span_metrics(tmp_path, monkeypatch):
    # as test_chip_bench_run's traced run: the device side is a trace
    # recorded on a TPU, the rest of the run is the CPU's own
    recorded = trace_reduce.load(str(TPU_TRACE))
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    root = make_tree(tmp_path)
    out = harness.run_cell("smoke.image_fastv", 2 ** 31 + 13, 2.0, True,
                           t_start=time.perf_counter(), repo=tmp_path,
                           root=root, require_chip=False)
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in READERS:
        assert got[name] > 0, name
    assert got["step_host_ms"] < got["engine_step_ms"]
    assert 1 <= got["decode_rows_mean"] <= 4           # max_batch 4
