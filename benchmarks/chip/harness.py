"""Runs one cell once: set-up, an open-loop window, the output check.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``)
and a traffic mix (``traffic/<name>.json``); its limits are in
``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``. Nothing here names a cell, a mix or a metric.

The window drives ``LVLM.serve_async`` (``AsyncLVLMServer`` over
``Engine.step``) greedily at temperature 0. Each request is a coroutine
that submits at its due time on the wall clock and timestamps every
token as it reaches it; times count from the due time, so a step that
holds the event loop delays both the submit and the tokens, as it would
delay a client of a real server.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

from . import correctness, flops, peaks, stats, trace_reduce, traffic, weights

ROOT = Path(__file__).resolve().parent
REPO = ROOT.parents[1]

DRAIN_S = 60.0          # how long past the close a due request may take
TRACE_S = 3.0           # the traced part of a --trace 1 window, at most

# published key -> ModelConfig field, for the keys the program takes
_PROGRAM_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype", "visual_tokens": "num_visual_tokens",
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ------------------------------------------------------------- loading --
def load_benchmark(repo: Path = REPO) -> Dict:
    with open(Path(repo) / "BENCHMARK.json") as f:
        return json.load(f)


def cell_of(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(bench: Dict, repo: Path, name: str) -> Dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(Path(repo) / entry["file"]) as f:
        return json.load(f)


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_reader(root: Path, name: str):
    """The module ``metrics/<name>.py`` with its ``read(ctx)``."""
    path = Path(root) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chip_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed32(seed: int) -> int:
    """A 31-bit key for JAX from any non-negative seed."""
    return int(traffic.rng_for(seed, "weights").integers(0, 2 ** 31 - 1))


def served_model(conf: Dict) -> Dict:
    """The published ``model`` with the values of ``departures.served`` in
    place of the published ones: what the program computes, and so what
    the reference follows."""
    return {**conf["model"], **conf.get("departures", {}).get("served", {})}


def program_config(conf: Dict):
    """The program's ``ModelConfig`` with every size the file states."""
    from repro.configs import get_config
    m = served_model(conf)
    over = {_PROGRAM_KEYS[k]: v for k, v in m.items() if k in _PROGRAM_KEYS}
    scaling = m.get("rope_scaling") or {}
    if scaling.get("type") == "mrope":
        over["mrope_sections"] = tuple(scaling["mrope_section"])
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError("only SwiGLU (silu) MLPs are served")
    return get_config(conf["arch"]).with_(**over)


# ------------------------------------------------------------- records --
@dataclasses.dataclass
class Record:
    plan: traffic.Planned
    due: float = 0.0                  # absolute perf_counter seconds
    sent: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    stream: object = None

    @property
    def finished(self) -> bool:
        return (self.error is None
                and len(self.tokens) == self.plan.max_new_tokens)


class CompileCounter:
    """Counts programs compiled or fetched from the persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.n += 1


class Probes:
    """Spans the harness writes around its calls into the engine, and
    the sizes of the prefill and decode calls made while tracing. Only
    installed in ``--trace 1`` runs; a timed run drives the bare engine."""

    def __init__(self, eng):
        import jax
        self._ann = jax.profiler.TraceAnnotation
        self.recording = False
        self.prefill: List[tuple] = []    # (n_text, n_visual) per call
        self.decode: List[list] = []      # live contexts per call
        self.shapes: Dict[str, tuple] = {}
        cache_len = eng.ec.cache_len
        pre, dec, step = eng._jit_prefill, eng._jit_decode, eng.step

        def prefill(p, batch):
            if self.recording:
                nv = (batch["visual_embeds"].shape[1]
                      if "visual_embeds" in batch else 0)
                self.prefill.append((batch["tokens"].shape[1], nv))
            self.shapes.setdefault("prefill", _abstract((p, batch)))
            with self._ann("bench:prefill program"):
                return pre(p, batch)

        def decode(p, pool, toks, pos):
            if self.recording:
                live = np.asarray(pos)
                self.decode.append([int(x) + 1 for x in live
                                    if x != cache_len - 1])
            self.shapes.setdefault("decode", _abstract((p, pool, toks, pos)))
            with self._ann("bench:decode program"):
                return dec(p, pool, toks, pos)

        def engine_step():
            with self._ann("bench:engine step (host)"):
                return step()

        eng._jit_prefill, eng._jit_decode, eng.step = prefill, decode, \
            engine_step
        self._jits = {"prefill": pre, "decode": dec}
        for name, comp in list(eng._compressors.items()):
            if getattr(comp, "encoder_active", False):
                comp.compress_prefill = self._span(
                    "bench:compress", comp.compress_prefill)

    def _span(self, name, fn):
        def wrapped(*a, **kw):
            with self._ann(name):
                return fn(*a, **kw)
        return wrapped

    def module_names(self) -> Dict[str, str]:
        """The XLA module name of each program, from lowering it at the
        shapes it was called with."""
        import re
        out = {}
        for name, args in self.shapes.items():
            text = self._jits[name].lower(*args).as_text()
            hit = re.search(r"module @(\S+)", text)
            if hit:
                out[name] = hit.group(1)
        return out


def _abstract(tree):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                                       a.dtype), tree)


# ---------------------------------------------------------------- run --
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, repo: Path = REPO, root: Path = ROOT,
             require_chip: bool = True, rate: Optional[float] = None,
             control: bool = False) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``rate`` overrides the mix's rate (the knee sweep); ``control`` adds
    the float8 control's reading (calibration). A benchmark run passes
    neither."""
    import jax

    bench = load_benchmark(repo)
    cell = cell_of(bench, workload)
    conf = config_of(bench, repo, cell["config"])
    mix = traffic.load_mix(root, cell["traffic"])
    if rate is not None:
        mix = dict(mix, rate_per_s=rate)
    limits = correctness.load_limits(root, workload)
    marks = {"imports": time.perf_counter()}
    devs = jax.devices()
    marks["device"] = time.perf_counter()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} "
                     f"({devs[0].device_kind})")
    pk = peaks.peaks_for(devs[0].device_kind) if require_chip else None

    from repro.api import EngineConfig, GenerationConfig, LVLM, Request
    from repro.models.registry import build
    from repro.obs import Profiler

    m = conf["model"]
    m_ref = served_model(conf)
    kept = traffic.visual_kept(mix)
    cfg = program_config(conf)
    model = build(cfg)
    params = weights.make(m, cfg.dtype, seed32(seed))
    if not weights.same_layout(params, jax.eval_shape(
            model.init, jax.random.PRNGKey(0))):
        raise ValueError("the program's parameter layout is not the "
                         "reference's (weights.layout)")
    jax.block_until_ready(params)
    marks["weights"] = time.perf_counter()
    lvlm = LVLM(model, params)
    ec = EngineConfig(max_batch=conf["engine"]["max_batch"],
                      cache_len=conf["engine"]["cache_len"],
                      scheduler="continuous")
    gen = GenerationConfig(decoder="greedy", temperature=0.0,
                           compression=mix["compression"])
    profiler = Profiler() if trace else None
    server = lvlm.serve_async(ec, gen=gen, profile=profiler)
    eng = server.engine
    probes = Probes(eng) if trace else None
    marks["engine"] = time.perf_counter()
    compiles = CompileCounter()
    images = traffic.image_pool(mix, seed, m["hidden_size"])
    plan = traffic.schedule(mix, seed, seconds, m["vocab_size"])
    warm = traffic.warmup_prompts(mix, seed, m["vocab_size"])
    if traffic.max_request_tokens(mix) > ec.cache_len - 1:
        raise ValueError("a request of this mix does not fit cache_len")

    def request(p: traffic.Planned):
        return Request(rid=p.rid, tokens=[int(t) for t in p.tokens],
                       max_new_tokens=p.max_new_tokens,
                       visual_embeds=None if p.image is None
                       else images[p.image])

    records = [Record(p) for p in plan]
    snaps: Dict[str, Dict] = {}
    trace_dir = tempfile.mkdtemp(prefix="chip_trace_") if trace else None
    clock = time.perf_counter

    def snapshot() -> Dict:
        return {"t": clock(), "iters": eng.iters, "compiles": compiles.n,
                "sites": profiler.snapshot() if profiler else {}}

    async def consume(rec: Record, req) -> None:
        stream = server.submit(req)
        rec.stream = stream
        try:
            async for tok in stream:
                rec.times.append(clock())
                rec.tokens.append(int(tok))
        except Exception as exc:                      # noqa: BLE001
            rec.error = repr(exc)

    async def client(rec: Record) -> None:
        await asyncio.sleep(max(0.0, rec.due - clock()))
        rec.sent = clock()
        await consume(rec, request(rec.plan))

    def start_trace():
        jax.block_until_ready(eng.pool)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        ann.__enter__()
        probes.recording = True
        return ann

    def stop_trace(ann) -> None:
        jax.block_until_ready(eng.pool)
        probes.recording = False
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    async def drive():
        async with server:
            warm_recs = [Record(p) for p in warm]
            await asyncio.gather(*(consume(r, request(r.plan))
                                   for r in warm_recs))
            bad = [r for r in warm_recs if not r.finished]
            if bad:
                raise RuntimeError(f"warm-up failed: {bad[0].error}")
            names = probes.module_names() if trace else {}
            t0 = clock()
            marks["warm-up"] = t0
            snaps["start"] = snapshot()
            for r in records:
                r.due = t0 + r.plan.due_s
            tasks = [asyncio.ensure_future(client(r)) for r in records]
            if trace:
                # the last part of the window; writing the trace out
                # holds the event loop, so it comes after the close
                t_trace = t0 + seconds - min(TRACE_S, seconds / 3.0)
                await asyncio.sleep(max(0.0, t_trace - clock()))
                ann = start_trace()
            await asyncio.sleep(max(0.0, t0 + seconds - clock()))
            snaps["end"] = snapshot()
            if trace:
                stop_trace(ann)
            await asyncio.wait(tasks, timeout=max(
                0.0, t0 + seconds + DRAIN_S - clock()))
            for r in records:
                if not r.finished and r.stream is not None:
                    r.stream.cancel()
            await asyncio.gather(*tasks)
            for r in records:
                r.stream = None         # a stream holds the server
            jax.block_until_ready(eng.pool)
            return t0, names

    try:
        t0, names = asyncio.run(drive())
        if trace:
            xp = trace_reduce.find_xplane(trace_dir)
            if xp is None:
                raise RuntimeError("the profiler wrote no trace")
            traced = trace_reduce.load(xp)
    finally:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    t_close = t0 + seconds
    prev, phases = t_start, []
    for name, t in marks.items():
        phases.append(f"{name} {t - prev:.3f}")
        prev = t
    print(f"set-up (s): {', '.join(phases)}", file=sys.stderr, flush=True)
    diagnose(records, t0, t_close, snaps)
    dev_stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(dev_stats.get("peak_bytes_in_use",
                                                     0))}

    # per-layer context, before the program's state goes
    ctx = SimpleNamespace(
        records=records, t0=t0, t_close=t_close, seconds=seconds,
        model=m, mix=mix, visual_kept=kept, peaks=pk, flops=flops,
        stats=stats, trace_reduce=trace_reduce,
        start=snaps["start"], end=snaps["end"], trace=None,
        probes=probes, modules=names)
    result_metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        ctx.trace = traced
        progs = trace_reduce.program_seconds(ctx.trace)
        print(f"traced programs {progs}; engine programs {names}; calls "
              f"prefill {len(ctx.probes.prefill)} decode "
              f"{len(ctx.probes.decode)}", file=sys.stderr, flush=True)
        busy = trace_reduce.busy_seconds(ctx.trace)
        lo, hi = ctx.trace.window
        device["busy_s"] = busy
        device["window_s"] = hi - lo
        breakdown = {"device_ops": trace_reduce.top_ops(ctx.trace),
                     "idle_gaps": trace_reduce.idle_gaps(ctx.trace)}
        for metric in bench["per_layer"]:
            if not applies(metric, workload):
                continue
            value = load_reader(root, metric["name"]).read(ctx)
            if value is not None:
                result_metrics[metric["name"]] = {"value": value,
                                                  "unit": metric["unit"]}
    else:
        e2e = end_to_end(records, t0, seconds, t_start)
        for metric in bench["end_to_end"]:
            if applies(metric, workload):
                result_metrics[metric["name"]] = {
                    "value": e2e[metric["name"]], "unit": metric["unit"]}

    # the output check: free the program's state, then the reference
    done = [correctness.Served(
        r.plan.rid, [int(t) for t in r.plan.tokens],
        None if r.plan.image is None else images[r.plan.image],
        list(r.tokens)) for r in records if r.finished]
    unfinished = sum(not r.finished for r in records)
    del server, eng, lvlm, probes, ctx
    gc.collect()
    picked = correctness.sample(done, seed, int(limits["sample"]))
    kw = dict(visual_kept=kept, pad_len=int(conf["engine"]["cache_len"]),
              max_served=int(mix["output"]["max"]))
    t_ref = clock()
    gap = correctness.widest(correctness.gaps(m_ref, params, picked, **kw))
    print(f"reference: {len(picked)} requests, "
          f"{sum(len(s.served) for s in picked)} served tokens, "
          f"{clock() - t_ref} s", file=sys.stderr, flush=True)
    correct, checks = correctness.verdict(gap, unfinished, len(picked),
                                          limits)
    out = {"correct": correct, "attempted": len(records),
           "failed": unfinished, "metrics": result_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if rate is not None:
        out["backlog"] = {"half": backlog(records, t0 + seconds / 2),
                          "close": backlog(records, t_close)}
    if control:
        # the control's first choices in place of the served tokens,
        # judged by the same verdict
        ctl = correctness.widest(correctness.gaps(
            m_ref, params, picked, quant="fp8", **kw))
        ok, ctl_checks = correctness.verdict(ctl, unfinished, len(picked),
                                             limits)
        out["control"] = {"correct": ok, "checks": ctl_checks}
    out["checks"] = checks
    return out


def end_to_end(records: List[Record], t0: float, seconds: float,
               t_start: float) -> Dict[str, float]:
    """The cell's end-to-end metrics over every request due in the window.

    A request with no first token by the end of the drain counts with
    the time it had waited then, so a miss lies in the tail."""
    t_close = t0 + seconds
    t_cut = t_close + DRAIN_S
    ttft, gaps, delivered = [], [], 0
    for r in records:
        first = r.times[0] if r.times else t_cut
        ttft.append((first - r.due) * 1e3)
        for a, b in zip(r.times, r.times[1:]):
            if b <= t_close:
                gaps.append((b - a) * 1e3)
        delivered += sum(t0 <= t <= t_close for t in r.times)
    return {
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "itl_p50_ms": stats.percentile(gaps, 50),
        "itl_p95_ms": stats.percentile(gaps, 95),
        "output_tokens_per_s": delivered / seconds,
        "setup_s": t0 - t_start,
    }


def diagnose(records: List[Record], t0: float, t_close: float,
             snaps: Dict) -> None:
    """One line on standard error of what can stall a window: the longest
    pause in token deliveries across all clients, the compiles, and the
    slowest first tokens."""
    times = sorted(t for r in records for t in r.times if t0 <= t <= t_close)
    pause, at = max(((b - a, a - t0) for a, b in zip(times, times[1:])),
                    default=(0.0, 0.0))
    worst = sorted(((r.times[0] if r.times else t_close) - r.due, r.due - t0)
                   for r in records)[-3:]
    print(f"window: longest delivery pause {pause:.4f} s at +{at:.2f} s; "
          f"compiles {snaps['end']['compiles'] - snaps['start']['compiles']}; "
          f"slowest first tokens (s, due at +s) "
          f"{[(round(a, 3), round(b, 2)) for a, b in worst]}",
          file=sys.stderr, flush=True)


def backlog(records: List[Record], t: float) -> int:
    """Requests due by ``t`` that had not received their first token."""
    return sum(r.due <= t and not (r.times and r.times[0] <= t)
               for r in records)


def print_result(out: Dict) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error; then the result as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
