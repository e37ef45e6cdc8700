"""The benchmark's data plumbing and arithmetic, without a model."""
from __future__ import annotations

import json
import re
from types import SimpleNamespace

import numpy as np
import pytest

from chip_bench_smoke import CHIP, REPO, TPU_TRACE, make_tree
from benchmarks.chip import harness, peaks, stats, trace_reduce, traffic

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
MIX = traffic.load_mix(CHIP, "image_chat")


def test_schedule_is_a_function_of_the_seed():
    a = traffic.schedule(MIX, 2 ** 31 + 7, 20.0, 151936)
    b = traffic.schedule(MIX, 2 ** 31 + 7, 20.0, 151936)
    c = traffic.schedule(MIX, 2 ** 31 + 8, 20.0, 151936)
    key = lambda plan: [(p.due_s, p.tokens.tolist(), p.max_new_tokens,
                         p.image) for p in plan]
    assert key(a) == key(b)
    assert key(a) != key(c)


def test_every_seed_gets_the_same_work_in_another_order():
    plans = [traffic.schedule(MIX, s, 30.0, 151936) for s in (1, 2, 3)]
    for plan in plans:
        assert len(plan) == round(MIX["rate_per_s"] * 30.0)
        assert all(0 < p.due_s < 30.0 for p in plan)
    for field in (lambda p: len(p.tokens), lambda p: p.max_new_tokens):
        sets = [sorted(field(p) for p in plan) for plan in plans]
        assert sets[0] == sets[1] == sets[2]
    # one arrival process for every seed: the same due times
    dues = [[p.due_s for p in plan] for plan in plans]
    assert dues[0] == dues[1] == dues[2]


def test_an_image_mix_states_the_visual_rows_it_keeps(tmp_path):
    # an uncompressed mix keeps every visual row; a compressed one says
    assert MIX["compression"] == "none" and "visual_kept" not in MIX
    assert traffic.visual_kept(MIX) == MIX["visual_tokens"]
    assert traffic.visual_kept(traffic.load_mix(CHIP, "image_chat_fastv")) \
        == 512
    (tmp_path / "traffic").mkdir()
    squeezed = dict(MIX, compression="fastv-0.5")
    (tmp_path / "traffic" / "squeezed.json").write_text(
        json.dumps(squeezed))
    with pytest.raises(ValueError, match="visual_kept"):
        traffic.load_mix(tmp_path, "squeezed")
    assert traffic.visual_kept(traffic.load_mix(CHIP, "text_chat")) == 0


def test_output_lengths_follow_the_mix():
    lens = traffic.output_lengths(MIX["output"], 1000)
    assert lens.min() >= MIX["output"]["min"]
    assert lens.max() <= MIX["output"]["max"]
    assert abs(np.median(lens) - MIX["output"]["median"]) <= 1


def test_a_traffic_file_added_in_a_directory_is_found_by_name(tmp_path):
    (tmp_path / "traffic").mkdir()
    mix = dict(MIX, rate_per_s=2.5, text_lengths=[17])
    (tmp_path / "traffic" / "new_mix.json").write_text(json.dumps(mix))
    got = traffic.load_mix(tmp_path, "new_mix")
    plan = traffic.schedule(got, 5, 4.0, 1000)
    assert len(plan) == 10 and {len(p.tokens) for p in plan} == {17}


def test_a_metric_reader_added_in_a_directory_is_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx.seconds * 2\n")
    mod = harness.load_reader(tmp_path, "new.metric")
    assert mod.read(SimpleNamespace(seconds=3.0)) == 6.0


def test_a_cell_added_as_files_runs_through_the_loaders(tmp_path):
    root = make_tree(tmp_path)
    bench = harness.load_benchmark(tmp_path)
    cell = harness.cell_of(bench, "smoke.text")
    conf = harness.config_of(bench, tmp_path, cell["config"])
    assert conf["arch"] == "phi4-mini-3.8b"
    assert traffic.load_mix(root, cell["traffic"])["compression"] == "none"
    cfg = harness.program_config(conf)
    assert (cfg.num_layers, cfg.d_model, cfg.vocab_size) == (2, 128, 512)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_reader_declares_what_benchmark_json_says(metric):
    mod = harness.load_reader(CHIP, metric["name"])
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        metric["layer"], metric["unit"], metric["source"], metric["moves"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    # every cell that reports this metric reports what it moves
    for cell in BENCH["workloads"]:
        if harness.applies(metric, cell["name"]):
            assert harness.applies(e2e[metric["moves"]], cell["name"])


def test_every_cell_has_its_files_and_its_metrics():
    names = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    for cell in BENCH["workloads"]:
        assert names.match(cell["name"]) and len(cell["why"]) <= 200
        conf = harness.config_of(BENCH, REPO, cell["config"])
        mix = traffic.load_mix(CHIP, cell["traffic"])
        lim = json.loads((CHIP / "limits" / f"{cell['name']}.json")
                         .read_text())
        assert lim["logit_gap"] > 0 and lim["sample"] >= 2
        assert traffic.max_request_tokens(mix) < conf["engine"]["cache_len"]
        e2e = [m for m in BENCH["end_to_end"]
               if harness.applies(m, cell["name"])]
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert any(harness.applies(m, cell["name"])
                   for m in BENCH["per_layer"])


def _rec(due, times, n_out):
    plan = traffic.Planned(0, 0.0, np.zeros(4, np.int32), n_out, None)
    return harness.Record(plan, due=due, times=list(times),
                          tokens=list(range(len(times))))


def test_tails_count_every_request_and_misses_land_in_the_tail():
    t0, sec = 100.0, 10.0
    recs = [_rec(t0 + i, [t0 + i + 0.1, t0 + i + 0.2], 2)
            for i in range(9)]
    recs.append(_rec(t0 + 9.5, [], 2))          # never got a token
    e2e = harness.end_to_end(recs, t0, sec, t_start=40.0)
    miss = (t0 + sec + harness.DRAIN_S - (t0 + 9.5)) * 1e3
    assert e2e["ttft_p90_ms"] == pytest.approx(
        stats.percentile([100.0] * 9 + [miss], 90))
    assert e2e["ttft_p90_ms"] > 100.0
    assert e2e["setup_s"] == pytest.approx(60.0)


def test_rates_and_gaps_are_over_the_whole_window():
    t0, sec = 0.0, 4.0
    recs = [_rec(0.0, [0.5, 1.0, 1.5, 5.0], 4),   # last token after close
            _rec(1.0, [1.2, 3.2], 2)]
    e2e = harness.end_to_end(recs, t0, sec, t_start=-1.0)
    assert e2e["output_tokens_per_s"] == pytest.approx(5 / 4.0)
    # gaps whose later token lies after the close are left out
    assert e2e["itl_p50_ms"] == pytest.approx(500.0)
    assert e2e["itl_p95_ms"] == pytest.approx(
        stats.percentile([500.0, 500.0, 2000.0], 95))


def test_percentile_and_spread_arithmetic():
    xs = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(np.percentile(xs, 90))
    assert stats.spread([9.0, 10.0, 10.0, 11.0]) == pytest.approx(
        (10.75 - 9.25) / 10.0)


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")


def test_trace_arithmetic_on_plain_events():
    tr = trace_reduce.Trace(
        window=(0.0, 1.0),
        ops=[("a", 0.0, 0.2), ("b", 0.1, 0.3), ("a", 0.6, 0.7),
             ("c", 0.95, 1.2)],
        programs=[("jit_p", 0.0, 0.3), ("jit_p", 0.6, 0.7),
                  ("jit_q", 0.95, 1.2)],
        host=[("engine step (host)", 0.0, 0.8),
              ("sample", 0.35, 0.5)],
        n_devices=1)
    assert trace_reduce.busy_seconds(tr) == pytest.approx(0.45)
    assert trace_reduce.program_seconds(tr) == {
        "jit_p": (2, pytest.approx(0.4))}
    assert trace_reduce.top_ops(tr)[0] == ["jit_p/a", pytest.approx(0.3)]
    assert trace_reduce.op_name("%fusion.3 = bf16[2] fusion(x)") == "fusion.3"
    gaps = trace_reduce.idle_gaps(tr)
    assert gaps[0] == ["sample", pytest.approx(0.3)]
    assert gaps[1] == ["engine step (host)", pytest.approx(0.25)]
    assert len(gaps) == 2


def test_a_trace_recorded_on_a_tpu_reduces_to_its_programs_and_gaps():
    tr = trace_reduce.load(str(TPU_TRACE))
    assert tr.n_devices == 1
    lo, hi = tr.window
    # the device's clock reads about a millisecond behind the host's, so
    # the first of the four runs falls before the window opens
    runs, secs = trace_reduce.program_seconds(tr)["jit_tiny_step"]
    assert runs == 3 and 0 < secs < hi - lo
    busy = trace_reduce.busy_seconds(tr)
    assert 0 < busy <= secs + 1e-9
    top = trace_reduce.top_ops(tr)
    assert top and all(name.startswith("jit_tiny_step/") for name, _ in top)
    # the three 3 ms host sleeps between runs are the longest idle gaps
    gaps = trace_reduce.idle_gaps(tr, n=3)
    assert [g[0] for g in gaps] == ["engine step (host)"] * 3
    assert all(g[1] >= 0.003 for g in gaps)


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="/device:TPU"):
        trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
