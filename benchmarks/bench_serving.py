"""Benchmark: serving & scheduling (survey dim 2c), via the ``repro.api``
facade.

Real engine, real smoke model, virtual-clock metrics:
  * scheduler comparison on a bursty mixed-length workload,
  * prefix caching on shared-system-prompt traffic,
  * per-request decoder mixing: greedy + sampling + speculative +
    early-exit requests in ONE engine run (batched speculative slots),
  * per-request COMPRESSION mixing (``--compression a,b``): VLM traffic
    cycling strategies in one engine through the async server, emitting
    per-strategy prefill-token-reduction in a ``# open_loop`` record,
  * open-loop Poisson traffic through the ASYNC serving stack at EVERY
    replica count (cluster Router, least-KV routing, SLO-slack deferred
    queues): one ``# open_loop`` JSON record per (rate, replica count)
    with fleet-wide percentiles + SLO attainment -- the multi-replica
    throughput/latency trajectory (``--replicas 1,2,4`` to extend),
  * disaggregated vs colocated pools under KV-transfer cost (analytic sim).

Latency rows report percentiles (p50/p95/p99), not just means.
"""
from __future__ import annotations

import asyncio
import json

import numpy as np

from benchmarks.common import emit
from repro.api import (AdmissionConfig, CostModel, EngineConfig,
                       GenerationConfig, LVLM, PoolConfig, Request, goodput,
                       simulate_colocated, simulate_disaggregated)
from repro.launch.cache import enable_compile_cache


def _pcts(out, metric: str) -> str:
    return ";".join(f"{metric}_p{p}={out.get(f'{metric}_p{p}') or 0:.4f}"
                    for p in (50, 95, 99))


def _reqs(cfg, n, seed=0, shared=0, lo=10, hi=60, new=8, gap=0.001):
    rng = np.random.RandomState(seed)
    pre = list(rng.randint(1, cfg.vocab_size, size=shared))
    return [Request(rid=i, tokens=pre + list(
        rng.randint(1, cfg.vocab_size, size=rng.randint(lo, hi))),
        max_new_tokens=new, arrival=i * gap) for i in range(n)]


def schedulers(lvlm: LVLM) -> None:
    for sched in ("static", "continuous", "mlfq", "chunked"):
        out = lvlm.serve(
            _reqs(lvlm.cfg, 12, seed=1),
            EngineConfig(max_batch=4, cache_len=128, scheduler=sched,
                         chunk_size=16, token_budget=48)).stats
        emit(f"serve/sched/{sched}", out["virtual_time_s"] * 1e6,
             f"{_pcts(out, 'ttft')};{_pcts(out, 'tpot')};"
             f"jct_mean={out['jct_mean']:.4f};"
             f"tput={out['throughput_tok_per_s']:.0f}")


def prefix_cache(lvlm: LVLM) -> None:
    for on in (False, True):
        out = lvlm.serve(
            _reqs(lvlm.cfg, 10, seed=2, shared=64, lo=4, hi=16, new=4),
            EngineConfig(max_batch=4, cache_len=192, prefix_cache=on,
                         prefix_block=16)).stats
        extra = (f"hit_rate={out.get('prefix_token_hit_rate', 0):.3f};"
                 if on else "")
        emit(f"serve/prefix_cache/{'on' if on else 'off'}",
             out["virtual_time_s"] * 1e6,
             extra + _pcts(out, 'ttft'))


def mixed_decoders(lvlm: LVLM) -> None:
    """One engine, four decode strategies concurrently (survey dim 4 at
    serving scale): per-request ``decoder`` mixing with batched speculative
    slots, vs the same workload served all-greedy."""
    strategies = ("speculative", "speculative", "speculative", "greedy",
                  "sampling", "early_exit", "greedy", "speculative")
    for label, decs in (("mixed", strategies),
                        ("all_greedy", ("greedy",) * len(strategies))):
        reqs = _reqs(lvlm.cfg, len(decs), seed=4, lo=8, hi=24, new=8,
                     gap=0.0005)
        for r, d in zip(reqs, decs):
            r.decoder = d
        out = lvlm.serve(
            reqs, EngineConfig(max_batch=4, cache_len=128,
                               temperature=0.0),
            gen=GenerationConfig(decoder="greedy", temperature=0.0,
                                 max_new_tokens=8, gamma=3)).stats
        spec = (f"spec_acc={out.get('speculative/acceptance', 0):.2f};"
                f"spec_slots={out.get('speculative/max_slots_per_round', 0)};"
                if label == "mixed" else "")
        emit(f"serve/mixed_decoders/{label}",
             out["virtual_time_s"] * 1e6,
             spec + f"{_pcts(out, 'ttft')};{_pcts(out, 'tpot')};"
             f"jct_mean={out['jct_mean']:.4f};"
             f"tput={out['throughput_tok_per_s']:.0f}")


def open_loop(lvlm: LVLM, replica_counts=(1, 2)) -> None:
    """Open-loop Poisson traffic through the ASYNC serving stack at every
    replica count: requests arrive over (virtual) time at a fixed rate,
    mixed decoder strategies, KV-watermark admission with SLO-slack
    deferred queues, routed over N engine replicas by least-committed-KV.
    One ``# open_loop`` JSON record per (rate, replica count) -- the
    fleet-wide throughput/latency trajectory BENCH_*.json tracks: tail
    TTFT/TPOT and SLO attainment under load, not closed-batch makespan."""
    strategies = ("speculative", "greedy", "sampling", "greedy")
    for label, rate in (("r500", 500.0), ("r2000", 2000.0)):
        for n_rep in replica_counts:
            rng = np.random.RandomState(9)
            reqs = _reqs(lvlm.cfg, 16, seed=10, lo=8, hi=24, new=8)
            arrivals = np.cumsum(rng.exponential(1.0 / rate,
                                                 size=len(reqs)))
            for i, r in enumerate(reqs):
                r.arrival = float(arrivals[i])
                r.decoder = strategies[i % len(strategies)]
            router = lvlm.serve_cluster(
                n_rep,
                EngineConfig(max_batch=4, cache_len=128, temperature=0.0),
                gen=GenerationConfig(decoder="greedy", temperature=0.0,
                                     max_new_tokens=8, gamma=3),
                routing="least_kv",
                admission=AdmissionConfig(high_watermark=0.9,
                                          low_watermark=0.7,
                                          order="slack"))

            async def drive(router=router, reqs=reqs):
                async def consume(r):
                    return [t async for t in router.submit(r)]
                async with router:
                    await asyncio.gather(*(consume(r) for r in reqs))
                return router.summary()

            out = asyncio.run(drive())
            emit(f"serve/open_loop/{label}/replicas{n_rep}",
                 out["virtual_time_s"] * 1e6,
                 f"{_pcts(out, 'ttft')};{_pcts(out, 'tpot')};"
                 f"slo_goodput={out['slo_goodput']:.2f};"
                 f"tput={out.get('fleet_throughput_tok_per_s', 0):.0f};"
                 f"queue_wait_p95={out.get('queue_wait_p95') or 0:.4f};"
                 f"deferred={out['deferred']}")
            record = {"scenario": f"open_loop/{label}/replicas{n_rep}",
                      "rate_rps": rate, "replicas": n_rep,
                      "routing": out["routing_policy"],
                      "finished": out["finished"],
                      "aborted": out["aborted"],
                      "slo_ttft_attainment": out["slo_ttft_attainment"],
                      "slo_tpot_attainment": out["slo_tpot_attainment"],
                      "slo_goodput": out["slo_goodput"],
                      "deferred": out["deferred"],
                      "failovers": out["failovers"],
                      "dispatched_by_replica": out["dispatched_by_replica"],
                      "fleet_throughput_tok_per_s":
                          out.get("fleet_throughput_tok_per_s"),
                      "virtual_time_s": out["virtual_time_s"]}
            record.update({k: out[k] for k in out if k.startswith(
                ("ttft_p", "tpot_p", "queue_wait_"))})
            print("# open_loop " + json.dumps(record, default=float),
                  flush=True)


def compression_mix(presets=("none", "fastv-0.5")) -> None:
    """Mixed-compression VLM workload: per-request ``Request.compression``
    cycles over ``presets`` in ONE engine (dim 1 at serving scale),
    open-loop Poisson arrivals through the async server. Emits one
    ``# open_loop`` JSON record whose ``prefill_token_reduction_by_
    strategy`` charts how much prefill each strategy saved -- the
    EffiVLM-BENCH-style sweep signal, measured on heterogeneous traffic
    instead of per-preset engine rebuilds."""
    vlm = LVLM.from_pretrained("qwen2-vl-2b", smoke=True)
    rng = np.random.RandomState(21)
    reqs = _reqs(vlm.cfg, 12, seed=22, lo=8, hi=20, new=6)
    arrivals = np.cumsum(rng.exponential(1.0 / 1000.0, size=len(reqs)))
    for i, r in enumerate(reqs):
        r.arrival = float(arrivals[i])
        r.visual_embeds = rng.randn(
            vlm.cfg.num_visual_tokens, vlm.cfg.d_model
        ).astype(np.float32) * 0.02
        r.compression = presets[i % len(presets)]
    server = vlm.serve_async(
        EngineConfig(max_batch=4, cache_len=128, temperature=0.0),
        gen=GenerationConfig(decoder="greedy", temperature=0.0,
                             max_new_tokens=6),
        admission=AdmissionConfig(high_watermark=0.9, low_watermark=0.7))

    async def drive():
        async def consume(r):
            return [t async for t in server.submit(r)]
        async with server:
            await asyncio.gather(*(consume(r) for r in reqs))
        return server.summary()

    out = asyncio.run(drive())
    reduction = {
        name.split("/")[1]: out[name]
        for name in out if name.startswith("compression/")
        and name.endswith("/prefill_token_reduction")}
    emit("serve/compression_mix/" + "+".join(presets),
         out["virtual_time_s"] * 1e6,
         ";".join(f"{n}={r:.2f}" for n, r in sorted(reduction.items()))
         + f";{_pcts(out, 'ttft')};finished={out['finished']}")
    record = {"scenario": "open_loop/compression_mix",
              "presets": list(presets),
              "finished": out["finished"],
              "prefill_token_reduction_by_strategy": reduction,
              "slo_goodput": out["slo_goodput"],
              "virtual_time_s": out["virtual_time_s"]}
    record.update({k: out[k] for k in out
                   if k.startswith(("ttft_p", "tpot_p"))})
    print("# open_loop " + json.dumps(record, default=float), flush=True)


def _wall_stats(events):
    """Per-request wall-clock latencies derived from tracer events: TTFT
    is the ``first_token`` instant minus the ``request`` span begin,
    TPOT the decode stretch (request end - first token) over the
    emitted tokens. These are the REAL elapsed times of the smoke-model
    run -- the profiling baseline BENCH_serving.json pins next to the
    cost-model's virtual-clock numbers."""
    begin, first, end, tokens = {}, {}, {}, {}
    for ev in events:
        if ev["name"] == "request" and ev["k"] == "B":
            begin[ev["rid"]] = ev["wt"]
        elif ev["name"] == "first_token":
            first[ev["rid"]] = ev["wt"]
        elif ev["name"] == "request" and ev["k"] == "E":
            end[ev["rid"]] = ev["wt"]
            tokens[ev["rid"]] = (ev.get("attrs") or {}).get("tokens", 0)
    ttft = [first[r] - begin[r] for r in first if r in begin]
    tpot = [(end[r] - first[r]) / (tokens[r] - 1)
            for r in end if r in first and tokens.get(r, 0) > 1]
    wts = [ev["wt"] for ev in events]
    return {"ttft": ttft, "tpot": tpot,
            "wall_time_s": (max(wts) - min(wts)) if wts else 0.0}


def wall_baseline(lvlm: LVLM, out_path: str, trace_out=None) -> None:
    """``--emit-bench``: one traced open-loop run on a disaggregated
    prefill/decode fleet, written as the schema-stable wall-clock
    profiling baseline ``BENCH_serving.json``.

    Schema (keys are stable; values vary with the host):
      schema_version            int, bumped on any key change
      scenario / roles / routing  what ran
      requests / finished / aborted / migrations  workload accounting
      virtual                   cost-model clock: time_s,
                                throughput_tok_per_s, ttft_s/tpot_s
                                {p50,p95}
      wall                      measured perf_counter: same keys --
                                the smoke-model profiling baseline
      profile                   Profiler.bench_record(): per hot-path
                                site call counts + wall self/total and
                                virtual seconds
    """
    from repro.obs import Profiler, Tracer, write_chrome_trace
    tracer = Tracer()
    profiler = Profiler()
    rng = np.random.RandomState(7)
    reqs = _reqs(lvlm.cfg, 16, seed=8, lo=8, hi=24, new=8)
    arrivals = np.cumsum(rng.exponential(1 / 2000.0, size=len(reqs)))
    for r, t in zip(reqs, arrivals):
        r.arrival = float(t)
    router = lvlm.serve_cluster(
        [{"role": "prefill"}, {"role": "decode"}],
        EngineConfig(max_batch=4, cache_len=128, temperature=0.0,
                     cost=CostModel(kv_bytes_per_token=100_000)),
        gen=GenerationConfig(decoder="greedy", temperature=0.0,
                             max_new_tokens=8),
        routing="least_kv", obs=tracer, profile=profiler)

    async def drive():
        async def consume(r):
            return [t async for t in router.submit(r)]
        async with router:
            await asyncio.gather(*(consume(r) for r in reqs))
        return router.summary()

    out = asyncio.run(drive())
    wall = _wall_stats(tracer.events)

    def _p(vals, p):
        return float(np.percentile(vals, p)) if vals else None

    tokens = out["tokens"]
    doc = {
        "schema_version": 1,
        "scenario": "open_loop/disagg_baseline",
        "roles": ["prefill", "decode"],
        "routing": out["routing_policy"],
        "requests": len(reqs),
        "finished": out["finished"],
        "aborted": out["aborted"],
        "migrations": out.get("disaggregation", {}).get("migrations", 0),
        "tokens": tokens,
        "virtual": {
            "time_s": out["virtual_time_s"],
            "throughput_tok_per_s": out.get("fleet_throughput_tok_per_s"),
            "ttft_s": {"p50": out.get("ttft_p50"),
                       "p95": out.get("ttft_p95")},
            "tpot_s": {"p50": out.get("tpot_p50"),
                       "p95": out.get("tpot_p95")},
        },
        "wall": {
            "time_s": wall["wall_time_s"],
            "throughput_tok_per_s": (tokens / wall["wall_time_s"]
                                     if wall["wall_time_s"] else None),
            "ttft_s": {"p50": _p(wall["ttft"], 50),
                       "p95": _p(wall["ttft"], 95)},
            "tpot_s": {"p50": _p(wall["tpot"], 50),
                       "p95": _p(wall["tpot"], 95)},
        },
        "profile": profiler.bench_record(),
    }
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, default=float)
        f.write("\n")
    if trace_out:
        write_chrome_trace(tracer.events, trace_out)
    print(f"# bench_baseline written to {out_path} "
          f"(wall {wall['wall_time_s']:.3f}s, "
          f"virtual {out['virtual_time_s'] * 1e3:.3f}ms)", flush=True)


def disagg_burst(lvlm: LVLM, trace_out=None) -> None:
    """Tentpole acceptance: a video-heavy prefill burst lands mid-run on
    a steady chat stream. Colocated replicas interleave the burst's
    chunked prefill with chat decode iterations, inflating chat TPOT; a
    ``--roles prefill:1,decode:1`` split keeps the decode replica's
    iterations prefill-free -- post-compression KV crosses the modeled
    link instead -- so the chat cohort's TPOT p95 stays within 10% of
    its no-burst baseline. Real engines, real migration, one
    ``# open_loop`` record per fleet with the degradation ratio."""
    cost = CostModel(kv_bytes_per_token=100_000)
    gen = GenerationConfig(decoder="greedy", temperature=0.0,
                           max_new_tokens=16)

    def _ec(batch):
        return EngineConfig(max_batch=batch, cache_len=512,
                            scheduler="chunked", chunk_size=32,
                            temperature=0.0, cost=cost)

    def _fleet(label, tracer=None):
        # equal aggregate slots (24) either way; the disagg fleet spends
        # them asymmetrically -- narrow prefill, wide decode batch
        if label == "disagg":
            return lvlm.serve_cluster(
                [{"role": "prefill", "engine_cfg": _ec(8)},
                 {"role": "decode", "engine_cfg": _ec(16)}],
                _ec(8), gen=gen, obs=tracer)
        return lvlm.serve_cluster(2, _ec(12), gen=gen, obs=tracer)

    def _workload(burst):
        rng = np.random.RandomState(33)
        chat = _reqs(lvlm.cfg, 16, seed=34, lo=8, hi=24, new=16)
        arr = np.cumsum(rng.exponential(1 / 2000.0, size=len(chat)))
        for r, t in zip(chat, arr):
            r.arrival = float(t)
        video = [Request(rid=100 + j, tokens=list(rng.randint(
            1, lvlm.cfg.vocab_size, size=420)), max_new_tokens=4,
            arrival=float(arr[4]) + j * 0.0005)
            for j in range(3)] if burst else []
        return chat, video

    def _chat_tpot_p95(chat):
        return float(np.percentile(
            [(r.finish_time - r.first_token_time)
             / max(1, len(r.generated) - 1) for r in chat], 95))

    for label in ("colocated", "disagg"):
        tpot, moved = {}, 0
        for phase in ("baseline", "burst"):
            tracer = None
            if trace_out and label == "disagg" and phase == "burst":
                # trace the interesting fleet: the burst crossing the
                # prefill->decode KV link (CI validates this trace)
                from repro.obs import Tracer
                tracer = Tracer()
            router = _fleet(label, tracer=tracer)
            chat, video = _workload(burst=(phase == "burst"))

            async def drive(router=router, reqs=chat + video):
                async def consume(r):
                    return [t async for t in router.submit(r)]
                async with router:
                    await asyncio.gather(*(consume(r) for r in reqs))
                return router.summary()

            out = asyncio.run(drive())
            tpot[phase] = _chat_tpot_p95(chat)
            if phase == "burst":
                moved = out.get("disaggregation", {}).get("migrations", 0)
            if tracer is not None:
                from repro.obs import write_chrome_trace
                write_chrome_trace(tracer.events, trace_out)
                print(f"# trace written to {trace_out} "
                      f"({len(tracer.events)} events)", flush=True)
        ratio = tpot["burst"] / tpot["baseline"]
        emit(f"serve/disagg_burst/{label}", tpot["burst"] * 1e6,
             f"chat_tpot_p95={tpot['burst']:.6f};"
             f"baseline={tpot['baseline']:.6f};ratio={ratio:.3f};"
             f"migrations={moved}")
        record = {"scenario": f"open_loop/disagg_burst/{label}",
                  "roles": (["prefill", "decode"] if label == "disagg"
                            else ["unified", "unified"]),
                  "chat_tpot_p95": tpot["burst"],
                  "chat_tpot_p95_no_burst": tpot["baseline"],
                  "degradation_ratio": ratio,
                  "within_10pct": bool(ratio <= 1.10),
                  "migrations": moved}
        print("# open_loop " + json.dumps(record, default=float),
              flush=True)


def control_burst(trace_out=None) -> None:
    """Adaptive-control acceptance: a video-heavy Poisson burst overloads
    a KV-tight server (every request carries 160 visual tokens at the
    ``none`` preset -- only ~2 fit the pool). Defer-only admission parks
    the overflow at the gate, so END-TO-END first-token latency (queue
    wait + TTFT, ``slo_e2e_attainment``) collapses; the SLO-adaptive
    controller (``control=``) degrades the deferred cohort to aggressive
    pruning presets instead -- smaller KV per request admits ~4x the
    concurrency and the queue drains. Identical workload, identical
    arrival rate, both runs; one ``# open_loop`` record per mode with the
    attainment + makespan comparison CI asserts on (controller-on must
    beat defer-only)."""
    from repro.api import ControlConfig, SLO
    vlm = LVLM.from_pretrained("qwen2-vl-2b", smoke=True)

    def _workload():
        rng = np.random.RandomState(77)
        reqs = _reqs(vlm.cfg, 16, seed=78, lo=8, hi=14, new=8)
        arrivals = np.cumsum(rng.exponential(1 / 4000.0, size=len(reqs)))
        for i, r in enumerate(reqs):
            r.arrival = float(arrivals[i])
            r.slo = SLO(ttft_ms=30.0, tpot_ms=6.0)
            r.visual_embeds = rng.randn(
                160, vlm.cfg.d_model).astype(np.float32) * 0.02
        return reqs

    results = {}
    for label, ctl in (("defer_only", None),
                       ("adaptive", ControlConfig(cooldown_s=0.001))):
        tracer = None
        if trace_out and label == "adaptive":
            from repro.obs import Tracer
            tracer = Tracer()
        reqs = _workload()
        server = vlm.serve_async(
            EngineConfig(max_batch=8, cache_len=256,
                         kv_capacity_tokens=512, temperature=0.0),
            gen=GenerationConfig(decoder="greedy", temperature=0.0,
                                 max_new_tokens=8),
            admission=AdmissionConfig(high_watermark=0.9,
                                      low_watermark=0.7),
            obs=tracer, control=ctl)

        async def drive(server=server, reqs=reqs):
            async def consume(r):
                return [t async for t in server.submit(r)]
            async with server:
                await asyncio.gather(*(consume(r) for r in reqs))
            return server.summary()

        out = asyncio.run(drive())
        results[label] = out
        if tracer is not None:
            from repro.obs import write_chrome_trace
            write_chrome_trace(tracer.events, trace_out)
            print(f"# trace written to {trace_out} "
                  f"({len(tracer.events)} events)", flush=True)
        emit(f"serve/control_burst/{label}",
             out["virtual_time_s"] * 1e6,
             f"e2e_attainment={out['slo_e2e_attainment']:.3f};"
             f"e2e_goodput={out['slo_e2e_goodput']:.3f};"
             f"queue_wait_p95={out.get('queue_wait_p95') or 0:.4f};"
             f"deferred={out['deferred']};"
             f"commits={out.get('control_commits', 0)}")
        record = {"scenario": f"open_loop/control_burst/{label}",
                  "rate_rps": 4000.0,
                  "finished": out["finished"],
                  "deferred": out["deferred"],
                  "slo_e2e_attainment": out["slo_e2e_attainment"],
                  "slo_e2e_goodput": out["slo_e2e_goodput"],
                  "slo_goodput": out["slo_goodput"],
                  "queue_wait_p95": out.get("queue_wait_p95"),
                  "e2e_ttft_p95": out.get("e2e_ttft_p95"),
                  "virtual_time_s": out["virtual_time_s"],
                  "control_commits": out.get("control_commits", 0),
                  "control_reverts": out.get("control_reverts", 0),
                  "control_overrides_open":
                      out.get("control_overrides_open", 0)}
        print("# open_loop " + json.dumps(record, default=float),
              flush=True)
    gain = (results["adaptive"]["slo_e2e_attainment"]
            - results["defer_only"]["slo_e2e_attainment"])
    print(f"# control_burst e2e attainment gain: {gain:+.3f} "
          f"(adaptive {results['adaptive']['slo_e2e_attainment']:.3f} "
          f"vs defer-only "
          f"{results['defer_only']['slo_e2e_attainment']:.3f})",
          flush=True)


def disaggregation() -> None:
    cost = CostModel(prefill_us_per_token=30.0, decode_us_per_token=600.0,
                     decode_us_per_ctx_token=0.01,
                     kv_bytes_per_token=500_000, transfer_gbps=20.0)
    for label, fn in (
            ("colocated", lambda rs: simulate_colocated(
                rs, cost, n_instances=2, decode_batch=16)),
            ("disagg", lambda rs: simulate_disaggregated(
                rs, cost, PoolConfig(1, 1, 16))),
            ("disagg_predlen", lambda rs: simulate_disaggregated(
                rs, cost, PoolConfig(1, 1, 16), predict_len=True))):
        rng = np.random.RandomState(3)
        reqs = [Request(rid=i, tokens=list(rng.randint(1, 64, size=rng.randint(
            100, 500))), max_new_tokens=int(rng.randint(8, 64)),
            arrival=i * 0.003) for i in range(32)]
        for r in reqs:
            r.predicted_len = r.max_new_tokens
        out = fn(reqs)
        g = goodput(reqs, ttft_slo=0.15, tpot_slo=0.002)
        emit(f"serve/disagg/{label}", out["makespan"] * 1e6,
             f"ttft_p99={out['ttft_p99']:.4f};tpot={out['tpot_mean']:.5f};"
             f"goodput={g:.2f}")


def run(replica_counts=(1, 2),
        compression=("none", "fastv-0.5")) -> None:
    lvlm = LVLM.from_pretrained("phi4-mini-3.8b", smoke=True)
    schedulers(lvlm)
    prefix_cache(lvlm)
    mixed_decoders(lvlm)
    compression_mix(presets=compression)
    open_loop(lvlm, replica_counts=replica_counts)
    disagg_burst(lvlm)
    control_burst()
    disaggregation()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--replicas", default="1,2",
                    help="comma-separated replica counts for the "
                         "open-loop trajectory (e.g. '2' or '1,2,4')")
    ap.add_argument("--compression", default="none,fastv-0.5",
                    help="comma-separated compression strategies for the "
                         "mixed-workload scenario (assigned per-request "
                         "round-robin, e.g. 'none,framefusion-0.25')")
    ap.add_argument("--only-open-loop", action="store_true",
                    help="skip the closed-loop scenarios")
    ap.add_argument("--only-disagg-burst", action="store_true",
                    help="run just the prefill/decode burst-isolation "
                         "scenario (the disaggregation smoke check)")
    ap.add_argument("--only-control-burst", action="store_true",
                    help="run just the SLO-adaptive controller vs "
                         "defer-only burst comparison (the repro.control "
                         "smoke check)")
    ap.add_argument("--emit-bench", default=None, metavar="PATH",
                    help="run the traced disaggregated baseline and write "
                         "the schema-stable wall+virtual profiling "
                         "baseline JSON (see wall_baseline docstring for "
                         "the schema) -- e.g. BENCH_serving.json")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the traced "
                         "scenario (--emit-bench run, or the disagg burst "
                         "with --only-disagg-burst); validate with "
                         "python -m repro.obs.validate")
    args = ap.parse_args()
    enable_compile_cache()
    counts = tuple(int(x) for x in str(args.replicas).split(",") if x)
    presets = tuple(p for p in str(args.compression).split(",") if p)
    if args.emit_bench:
        wall_baseline(LVLM.from_pretrained("phi4-mini-3.8b", smoke=True),
                      args.emit_bench, trace_out=args.trace_out)
    elif args.only_disagg_burst:
        disagg_burst(LVLM.from_pretrained("phi4-mini-3.8b", smoke=True),
                     trace_out=args.trace_out)
    elif args.only_control_burst:
        control_burst(trace_out=args.trace_out)
    elif args.only_open_loop:
        open_loop(LVLM.from_pretrained("phi4-mini-3.8b", smoke=True),
                  replica_counts=counts)
    else:
        run(replica_counts=counts, compression=presets)


if __name__ == "__main__":
    main()
