"""repro.obs.profile (PR tentpole): continuous hot-path profiling and
the perf-regression gate.

Contracts locked down here:

  * ZERO overhead when off: the default engine/server hold
    NULL_PROFILER and the hot path performs no profiler calls at all
    (every NullProfiler site method is patched to raise; full serve and
    disaggregated-cluster runs must not trip one),
  * profiling changes nothing: a profiled cluster run (sanitizer on) is
    bit-identical to the unprofiled run at temperature 0, while the
    profiler sees every hot-path site class (prefill forward, decode
    launch, KV export/transfer),
  * self/total attribution: nested sites subtract from the parent's
    self time, and the collapsed-stack export carries the nesting path,
  * the Prometheus histogram family shape (cumulative ``le`` buckets,
    ``+Inf``, ``_sum``/``_count``) and its single fleet-level rendering
    in ``Router.metrics_snapshot()``,
  * the committed ``BENCH_kernels.json`` baseline gates: self-compare
    exits 0, a synthetically slowed copy beyond tolerance exits 1
    (``python -m repro.obs.regress``),
  * ``scripts/profile_report.py`` (table + collapsed stacks),
    ``scripts/trace_report.py --json``, and warmup-correct
    ``benchmarks.common.time_jit`` min/mean/std stats.
"""
import asyncio
import importlib.util
import json
import os

import numpy as np
import pytest

import repro.obs.regress as regress
from repro.api import EngineConfig, GenerationConfig, LVLM, Request
from repro.core.serving.disaggregation import CostModel
from repro.obs import (NULL_PROFILER, NullProfiler, Profiler,
                       profile_families)
from repro.obs.profile import bucket_bounds
from repro.obs.prom import PromText

MAX_NEW = 6
GEN = GenerationConfig(decoder="greedy", temperature=0.0,
                       max_new_tokens=MAX_NEW)
COST = CostModel(kv_bytes_per_token=100_000)
REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def lvlm():
    return LVLM.from_pretrained("phi4-mini-3.8b", smoke=True)


def _ec(**kw):
    base = dict(max_batch=4, cache_len=96, temperature=0.0, sanitize=True)
    base.update(kw)
    return EngineConfig(**base)


def _prompts(n, seed=0, lo=8, hi=16):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, 512, size=rng.randint(lo, hi)))
            for _ in range(n)]


def _reqs(prompts, new=MAX_NEW):
    return [Request(rid=i, tokens=list(p), max_new_tokens=new)
            for i, p in enumerate(prompts)]


async def _consume(stream):
    return [tok async for tok in stream]


def _drive_all(front, reqs):
    async def drive():
        async with front:
            return await asyncio.gather(
                *(_consume(front.submit(r)) for r in reqs))

    outs = asyncio.run(drive())
    return {r.rid: list(o) for r, o in zip(reqs, outs)}


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- zero overhead when off --


def test_unprofiled_hot_path_makes_no_profiler_calls(lvlm, monkeypatch):
    """The default (unprofiled) stack must not call ANY profiler method
    -- guarded sites skip on ``enabled`` alone. Patching every
    NullProfiler site method to raise turns one stray call into a test
    failure (the NullTracer overhead test's twin)."""
    def boom(*a, **k):
        raise AssertionError("profiler method called on the unprofiled "
                             "path")

    for name in ("site_begin", "site_end", "site_drop", "wait_begin",
                 "wait_end", "interval_begin", "interval_end",
                 "interval_drop", "count"):
        monkeypatch.setattr(NullProfiler, name, boom)
    res = lvlm.serve(_reqs(_prompts(3, seed=1)), engine_cfg=_ec(), gen=GEN)
    assert res.engine.profiler is NULL_PROFILER
    assert res.stats["finished"] == 3
    # the cluster path too (migration exercises the kv_* sites)
    router = lvlm.serve_cluster(2, _ec(cost=COST), gen=GEN,
                                roles=["prefill", "decode"])
    got = _drive_all(router, _reqs(_prompts(2, seed=2)))
    assert all(len(o) == MAX_NEW for o in got.values())


def test_profiled_run_is_bit_identical_at_temp0(lvlm):
    """Profiling only reads clocks: same tokens, sanitizer clean, and
    every expected hot-path site class observed on a disaggregated
    fleet (prefill forward on the prefill replica, kv export/transfer
    across the link, decode launches on the decode replica, the step's
    phases and device waits, each request's queue wait, the pumps' host
    time)."""
    prompts = _prompts(4, seed=3)
    ref = _drive_all(lvlm.serve_cluster(2, _ec(cost=COST), gen=GEN,
                                        roles=["prefill", "decode"]),
                     _reqs(prompts))
    prof = Profiler()
    got = _drive_all(lvlm.serve_cluster(2, _ec(cost=COST), gen=GEN,
                                        roles=["prefill", "decode"],
                                        profile=prof),
                     _reqs(prompts))
    assert got == ref
    snap = prof.snapshot()
    for site in ("prefill_forward", "decode:greedy", "kv_export",
                 "kv_transfer", "engine_step", "schedule", "decode_inputs",
                 "retire", "wait:decode", "wait:prefill", "queue_wait",
                 "pump_host"):
        assert snap[site]["count"] > 0, site
        assert snap[site]["wall_total_s"] >= snap[site]["wall_self_s"] >= 0
        assert sum(n for _, n in snap[site]["wall_buckets"]) \
            == snap[site]["count"]
    assert snap["queue_wait"]["count"] == len(prompts)
    for site in ("wait:decode", "wait:prefill", "queue_wait", "pump_host"):
        assert snap[site]["stackless"], site
        assert snap[site]["wall_self_s"] == snap[site]["wall_total_s"]
    assert not snap["engine_step"]["stackless"]
    # every decode row is one token served after the first
    assert snap["decode_rows"]["count"] == snap["decode:greedy"]["count"]
    assert snap["decode_rows"]["total"] == len(prompts) * (MAX_NEW - 1)


# ------------------------------------------------- attribution mechanics --


def _manual_profiler():
    t = [0.0]
    prof = Profiler(clock=lambda: t[0])
    return prof, t


def test_profiler_self_total_nesting():
    prof, t = _manual_profiler()
    prof.site_begin("outer")
    t[0] = 1.0
    prof.site_begin("inner")
    t[0] = 3.0
    prof.site_end("inner")
    t[0] = 4.0
    prof.site_end("outer")
    snap = prof.snapshot()
    assert snap["outer"]["wall_total_s"] == pytest.approx(4.0)
    assert snap["outer"]["wall_self_s"] == pytest.approx(2.0)
    assert snap["inner"]["wall_total_s"] == pytest.approx(2.0)
    assert snap["inner"]["wall_self_s"] == pytest.approx(2.0)
    assert snap["outer"]["count"] == snap["inner"]["count"] == 1
    assert not snap["outer"]["stackless"]
    lines = prof.collapsed()
    assert "outer 2000000" in lines
    assert "outer;inner 2000000" in lines
    rec = prof.bench_record()
    assert rec["schema_version"] == 1
    assert rec["sites"]["outer"]["count"] == 1


def _prefill_shaped(prof, t, waits):
    """A step holding a prefill whose compression and first token each
    wait on the device; ``waits`` times those waits or leaves them be."""
    def tick(dt):
        t[0] += dt

    prof.site_begin("engine_step")
    tick(1.0)
    prof.site_begin("prefill_forward", rid=7)
    tick(1.0)
    prof.site_begin("compress")
    tick(0.5)
    if waits:
        prof.wait_begin("wait:compress")
    tick(2.0)
    if waits:
        prof.wait_end("wait:compress")
    prof.site_end("compress")
    tick(1.0)
    if waits:
        prof.wait_begin("wait:prefill")
    tick(3.0)
    if waits:
        prof.wait_end("wait:prefill")
    prof.site_end("prefill_forward")
    prof.site_end("engine_step")


def test_waits_leave_site_times_unchanged():
    """Device waits are stackless: timing them moves no site's self or
    total time (``prefill_call_ms`` reads ``prefill_forward``'s self
    time, ``compress_call_ms`` ``compress``'s total) and no collapsed
    stack."""
    plain, t0 = _manual_profiler()
    _prefill_shaped(plain, t0, waits=False)
    timed, t1 = _manual_profiler()
    _prefill_shaped(timed, t1, waits=True)
    a, b = plain.snapshot(), timed.snapshot()
    for site in ("engine_step", "prefill_forward", "compress"):
        for key in ("count", "wall_total_s", "wall_self_s"):
            assert a[site][key] == b[site][key], (site, key)
    assert b["prefill_forward"]["wall_self_s"] == pytest.approx(5.0)
    assert b["compress"]["wall_total_s"] == pytest.approx(2.5)
    assert b["wait:compress"]["wall_total_s"] == pytest.approx(2.0)
    assert b["wait:prefill"]["wall_total_s"] == pytest.approx(3.0)
    assert b["wait:prefill"]["stackless"]
    assert plain.collapsed() == timed.collapsed()


def test_site_drop_and_intervals():
    prof, t = _manual_profiler()
    prof.site_begin("outer")
    prof.site_begin("idle")
    t[0] = 2.0
    prof.site_drop("idle")                  # not recorded: outer's self
    prof.site_end("outer")
    prof.interval_begin("queue_wait", 1)
    t[0] = 3.0
    prof.interval_begin("queue_wait", 2)
    prof.interval_begin("queue_wait", 1)    # already open: keeps start
    t[0] = 4.0
    prof.interval_end("queue_wait", 1)
    prof.interval_drop("queue_wait", 2)     # left before it ended
    prof.interval_end("queue_wait", 2)      # nothing open: no-op
    prof.wait_begin("wait:decode")
    t[0] = 9.0
    prof.wait_begin("wait:decode")          # a stale wait is replaced
    t[0] = 10.0
    prof.wait_end("wait:decode")
    snap = prof.snapshot()
    assert "idle" not in snap
    assert snap["outer"]["wall_self_s"] == pytest.approx(2.0)
    assert snap["queue_wait"]["count"] == 1
    assert snap["queue_wait"]["wall_total_s"] == pytest.approx(2.0)
    assert snap["wait:decode"]["count"] == 1
    assert snap["wait:decode"]["wall_total_s"] == pytest.approx(1.0)
    assert prof.collapsed() == ["outer 2000000"]


def test_decode_sites_count_decode_launches(lvlm, monkeypatch):
    """``decode_batch_mean`` counts launches by the ``decode:`` sites:
    one per decoder launch, and no other site or counter starts with
    ``decode:``. ``decode_rows`` counts each launch's rows."""
    from repro.core.serving.engine import SamplingEngineDecoder
    launches = []
    inner = SamplingEngineDecoder.engine_decode

    def counted(self, eng, reqs):
        launches.append(len(reqs))
        return inner(self, eng, reqs)

    monkeypatch.setattr(SamplingEngineDecoder, "engine_decode", counted)
    prof = Profiler()
    res = lvlm.serve(_reqs(_prompts(3, seed=8), new=5), engine_cfg=_ec(),
                     gen=GEN, profile=prof)
    assert res.stats["finished"] == 3
    snap = prof.snapshot()
    decode_sites = [k for k in snap if k.startswith("decode:")]
    assert decode_sites == ["decode:greedy"]
    assert snap["decode:greedy"]["count"] == len(launches) > 0
    for name in ("decode_inputs", "wait:decode", "decode_rows"):
        assert snap[name]["count"] == len(launches), name
    assert snap["decode_rows"]["total"] == sum(launches) == 3 * 4
    assert snap["engine_step"]["count"] == res.engine.iters


@pytest.fixture(scope="module")
def vlm():
    return LVLM.from_pretrained("qwen2-vl-2b", smoke=True)


def test_profiled_serve_async_spans_land_in_the_jax_trace(vlm, tmp_path):
    """With a ``jax.profiler`` trace recording, each site is a
    ``repro:<site>`` span on the host plane of the same trace as the
    device's operations: prefills (with their request id) and decode
    input puts inside engine steps, one queue wait per request."""
    import jax
    from jax.profiler import ProfileData

    cfg = vlm.cfg
    rng = np.random.RandomState(9)
    reqs = [Request(rid=i, tokens=list(rng.randint(1, cfg.vocab_size, 9)),
                    max_new_tokens=3, compression="fastv-0.5",
                    visual_embeds=rng.randn(cfg.num_visual_tokens,
                                            cfg.d_model).astype(np.float32))
            for i in range(3)]
    prof = Profiler()
    server = vlm.serve_async(EngineConfig(max_batch=2, cache_len=96,
                                          temperature=0.0),
                             GEN, profile=prof)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = _drive_all(server, reqs)
    finally:
        jax.profiler.stop_trace()
    assert all(len(o) == 3 for o in got.values())
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro:"):
                    spans.setdefault(e.name[len("repro:"):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    steps = spans["engine_step"]

    def in_a_step(span):
        return any(a <= span[0] and span[1] <= b for a, b, _ in steps)

    assert len(steps) == prof.snapshot()["engine_step"]["count"]
    assert sorted(s[2]["rid"] for s in spans["prefill_forward"])         == [0, 1, 2]
    for name in ("prefill_forward", "decode_inputs", "compress",
                 "wait:compress", "wait:prefill", "wait:decode"):
        assert spans[name] and all(in_a_step(s) for s in spans[name]), name
    assert all(s[2]["rows"] >= 1 for s in spans["decode:greedy"])
    assert sorted(s[2]["rid"] for s in spans["queue_wait"]) == [0, 1, 2]
    assert spans["pump_host"]
    assert not any(in_a_step(s) for s in spans["pump_host"])


def test_profiler_log_buckets():
    bounds = bucket_bounds()
    assert all(b2 == 2 * b1 for b1, b2 in zip(bounds, bounds[1:]))
    prof, t = _manual_profiler()
    for dur in (1e-6, 3e-6, 3e-6, 0.5):
        t0 = t[0]
        prof.site_begin("s")
        t[0] = t0 + dur
        prof.site_end("s")
    buckets = {round(le, 9): n
               for le, n in prof.snapshot()["s"]["wall_buckets"] if n}
    assert buckets[round(1e-6, 9)] == 1            # <= base bound
    assert buckets[round(4e-6, 9)] == 2            # two 3us calls
    assert sum(buckets.values()) == 4


def test_profiler_mismatched_end_is_defensive():
    prof, t = _manual_profiler()
    prof.site_end("never_opened")                   # no-op, no raise
    prof.site_begin("outer")
    prof.site_begin("leaked")
    t[0] = 1.0
    prof.site_end("outer")                          # unwinds past "leaked"
    snap = prof.snapshot()
    assert "leaked" not in snap                     # discarded, not counted
    assert snap["outer"]["count"] == 1


# --------------------------------------------------- prometheus histogram --


def test_prom_histogram_rendering():
    prom = PromText()
    prom.histogram("lat_seconds", "Latency.", [(0.001, 2), (0.004, 1)],
                   0.0055, 4, labels={"site": "s"})
    prom.histogram("lat_seconds", "Latency.", [(0.001, 1)], 0.001, 1,
                   labels={"site": "t"})
    text = prom.render()
    assert text.count("# TYPE repro_lat_seconds histogram") == 1
    assert 'repro_lat_seconds_bucket{le="0.001",site="s"} 2' in text
    # cumulative: the 0.004 bucket includes the 0.001 bucket's count
    assert 'repro_lat_seconds_bucket{le="0.004",site="s"} 3' in text
    # +Inf always closes the family at the total count
    assert 'repro_lat_seconds_bucket{le="+Inf",site="s"} 4' in text
    assert 'repro_lat_seconds_sum{site="s"} 0.0055' in text
    assert 'repro_lat_seconds_count{site="s"} 4' in text
    assert 'repro_lat_seconds_bucket{le="+Inf",site="t"} 1' in text


def test_metrics_snapshot_renders_profile_once_per_fleet(lvlm):
    prof = Profiler()
    router = lvlm.serve_cluster(2, _ec(cost=COST), gen=GEN,
                                roles=["prefill", "decode"], profile=prof)
    got = _drive_all(router, _reqs(_prompts(3, seed=5)))
    assert all(len(o) == MAX_NEW for o in got.values())
    text = router.metrics_snapshot()
    # ONE fleet-level histogram family (the profiler is fleet-shared;
    # per-replica rendering would duplicate identical data)
    assert text.count("# TYPE repro_profile_wall_seconds histogram") == 1
    assert 'site="prefill_forward"' in text
    assert 'site="kv_transfer"' in text
    assert "repro_profile_wall_self_seconds_total" in text
    # a standalone (replica-less) server renders its own families
    server = lvlm.serve_async(_ec(), GEN, profile=Profiler())
    _drive_all(server, _reqs(_prompts(2, seed=6)))
    solo = server.metrics_snapshot()
    assert "# TYPE repro_profile_wall_seconds histogram" in solo
    # ...but not when labeled for a fleet scrape (the router owns it)
    assert "profile_wall_seconds" not in server.metrics_snapshot(replica=0)


def test_profile_families_helper():
    prof, t = _manual_profiler()
    prof.site_begin("a")
    t[0] = 0.25
    prof.site_end("a")
    prof.count("rows", 3)
    prof.count("rows", 5)
    prom = PromText()
    profile_families(prom, prof, labels={"cluster": "x"})
    text = prom.render()
    assert 'cluster="x"' in text
    assert "virtual" not in text
    assert 'repro_profile_wall_seconds_sum{cluster="x",site="a"} 0.25' \
        in text
    assert "# TYPE repro_profile_events_total counter" in text
    assert 'repro_profile_events_total{cluster="x",counter="rows"} 2.0' \
        in text
    assert 'repro_profile_counted_total{cluster="x",counter="rows"} 8.0' \
        in text


# -------------------------------------------------------- regression gate --


def test_regress_committed_kernel_baseline_self_compare():
    """Acceptance: the committed BENCH_kernels.json gates against
    itself cleanly, and a 3x-slowed copy beyond tolerance exits 1."""
    path = os.path.join(REPO, "BENCH_kernels.json")
    doc = json.load(open(path))
    assert doc["schema_version"] == 1
    kernels = {r["kernel"] for r in doc["rows"]}
    assert {"flash_attention", "paged_attention",
            "blockwise_sdpa"} <= kernels
    assert regress.main([path, path]) == 0


def test_regress_slowed_copy_fails(tmp_path):
    path = os.path.join(REPO, "BENCH_kernels.json")
    doc = json.load(open(path))
    for r in doc["rows"]:
        if "us_per_call" in r:
            r["us_per_call"] = {k: v * 3.0
                                for k, v in r["us_per_call"].items()}
    slow = str(tmp_path / "slow.json")
    json.dump(doc, open(slow, "w"))
    assert regress.main([slow, path, "--tolerance", "0.5"]) == 1
    # a FASTER copy is an improvement, never a regression
    for r in doc["rows"]:
        if "us_per_call" in r:
            r["us_per_call"] = {k: v / 9.0
                                for k, v in r["us_per_call"].items()}
    fast = str(tmp_path / "fast.json")
    json.dump(doc, open(fast, "w"))
    assert regress.main([fast, path, "--tolerance", "0.5"]) == 0


def test_regress_direction_heuristics():
    assert regress._direction("rows.k/s.us_per_call.min") == 1
    assert regress._direction("rows.k/s.us_per_call.std") == 0   # noise
    assert regress._direction("virtual.ttft_s.p50") == 1
    assert regress._direction("wall.throughput_tok_per_s") == -1
    assert regress._direction("stages.decode.share") == 0
    assert regress._direction("schema_version") == 0
    assert regress._direction("profile.sites.compress.wall_self_s") == 1
    assert regress._direction("requests") == 0
    # lower throughput regresses, higher does not
    regs, _ = regress.compare({"throughput_tok_per_s": 1.0},
                              {"throughput_tok_per_s": 3.0}, 0.5)
    assert len(regs) == 1
    regs, _ = regress.compare({"throughput_tok_per_s": 9.0},
                              {"throughput_tok_per_s": 3.0}, 0.5)
    assert regs == []
    # rows are matched by identity key, not list position
    a = {"rows": [{"kernel": "k1", "shape": "s", "us_per_call": {"min": 1}},
                  {"kernel": "k2", "shape": "s", "us_per_call": {"min": 5}}]}
    b = {"rows": [{"kernel": "k2", "shape": "s", "us_per_call": {"min": 5}},
                  {"kernel": "k1", "shape": "s", "us_per_call": {"min": 1}}]}
    regs, compared = regress.compare(a, b, 0.1)
    assert regs == [] and len(compared) == 2


def test_serving_baseline_has_profile_block():
    doc = json.load(open(os.path.join(REPO, "BENCH_serving.json")))
    assert doc["schema_version"] == 1
    sites = doc["profile"]["sites"]
    assert sites["prefill_forward"]["count"] > 0
    assert sites["kv_transfer"]["wall_total_s"] > 0.0
    assert all("virtual_s" not in s for s in sites.values())


# ---------------------------------------------------------- report tools --


def test_profile_report_table_and_collapsed(tmp_path, capsys):
    prof, t = _manual_profiler()
    prof.site_begin("prefill_forward")
    t[0] = 1.0
    prof.site_begin("compress")
    t[0] = 3.0
    prof.site_end("compress")
    t[0] = 4.0
    prof.site_end("prefill_forward")
    prof.count("decode_rows", 4)
    p = str(tmp_path / "profile.json")
    prof.write_json(p)
    pr = _load_script("profile_report")
    folded = str(tmp_path / "profile.folded")
    assert pr.main([p, "--collapsed", folded]) == 0
    out = capsys.readouterr().out
    assert "prefill_forward" in out and "compress" in out
    assert "decode_rows" in out and "total 4" in out
    lines = open(folded).read().splitlines()
    assert "prefill_forward;compress 2000000" in lines
    assert "prefill_forward 2000000" in lines


def test_trace_report_json_diffable(lvlm, tmp_path, capsys):
    from repro.obs import Tracer
    tracer = Tracer()
    router = lvlm.serve_cluster(2, _ec(cost=COST), gen=GEN,
                                roles=["prefill", "decode"], obs=tracer)
    got = _drive_all(router, _reqs(_prompts(3, seed=7)))
    assert all(len(o) == MAX_NEW for o in got.values())
    p = str(tmp_path / "events.jsonl")
    tracer.write_jsonl(p)
    tr = _load_script("trace_report")
    assert tr.main([p, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema_version"] == 1
    assert doc["requests"] == 3 and doc["aborted"] == 0
    shares = [s["share"] for s in doc["stages"].values()]
    assert sum(shares) == pytest.approx(1.0)
    assert doc["stages"]["kv_migration"]["mean_s"] > 0.0
    # two identical attribution documents diff clean through the gate
    a = str(tmp_path / "a.json")
    json.dump(doc, open(a, "w"))
    assert regress.main([a, a]) == 0
    # and a slower decode stage beyond tolerance fails it
    worse = json.loads(json.dumps(doc))
    for k in ("mean_s", "p50_s", "p95_s"):
        worse["stages"]["decode"][k] = doc["stages"]["decode"][k] * 4.0
    b = str(tmp_path / "b.json")
    json.dump(worse, open(b, "w"))
    assert regress.main([b, a, "--tolerance", "0.5"]) == 1


def test_time_jit_reports_min_mean_std():
    common_spec = importlib.util.spec_from_file_location(
        "bench_common", os.path.join(REPO, "benchmarks", "common.py"))
    common = importlib.util.module_from_spec(common_spec)
    common_spec.loader.exec_module(common)
    import jax.numpy as jnp
    x = jnp.arange(128.0)
    t = common.time_jit(lambda a: (a * 2).sum(), x, warmup=1, iters=4)
    assert isinstance(t, float)
    assert float(t) == t.min_us
    assert t.min_us <= t.mean_us
    assert t.std_us >= 0.0
    stats = t.stats()
    assert set(stats) == {"min", "mean", "std"}
    # the float value formats like the old scalar return (emit() rows)
    assert f"{t:.1f}" == f"{t.min_us:.1f}"
