"""Bring-up check: the serving main path on one TPU at published widths.

    python chip_smoke.py

Builds qwen2-vl-2b at its published widths through ``LVLM.from_pretrained``
(bf16, random weights from seed 0: the repository holds no weights) and
serves four image requests -- 1024 visual embeddings and 16-48 text tokens
each, two of them pruned with ``fastv-0.5`` -- closed loop through
``LVLM.serve`` and open loop through ``LVLM.serve_async`` (Poisson
arrivals). It checks that every request finished with in-vocabulary
tokens, that both loops returned the same tokens at temperature 0, that
the pruned requests reserved less KV, and that a direct prefill gives
finite logits whose argmax is the first served token.

Each phase prints its wall time (compilation included) and device on a
line of its own. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed. Without a TPU the script exits non-zero and
prints no result. ``run_phases`` is importable so the same phases run on
a CPU at the smoke config in the tests.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from functools import partial
from pathlib import Path

_SRC = str(Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import EngineConfig, GenerationConfig, LVLM  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import synth_requests  # noqa: E402

ARCH = "qwen2-vl-2b"
N_REQUESTS = 4
NEW_TOKENS = 16
CACHE_LEN = 2048
COMPRESSION = ("fastv-0.5", "none")     # alternated over the requests
OPEN_LOOP_RATE = 8.0                    # Poisson arrivals, req/s (virtual)


class SmokeFailure(RuntimeError):
    """A phase's output is wrong."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _device_line() -> str:
    dev = jax.devices()[0]
    return f"{dev.device_kind} ({dev.platform}) x{len(jax.devices())}"


class _Phase:
    """Context manager that prints one phase's wall time and device."""

    def __init__(self, name: str, times: dict):
        self.name, self.times = name, times

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            dt = time.perf_counter() - self.t0
            self.times[self.name] = dt
            print(f"phase {self.name}: {dt} s on {_device_line()}",
                  flush=True)
        return False


def _requests(cfg):
    """The smoke workload, made anew from seed 0 on every call."""
    reqs = synth_requests(cfg, N_REQUESTS, seed=0, new_tokens=NEW_TOKENS)
    for i, r in enumerate(reqs):
        r.compression = COMPRESSION[i % len(COMPRESSION)]
    return reqs


async def _consume(stream):
    return [tok async for tok in stream]


def run_phases(arch: str = ARCH, *, smoke: bool = False) -> dict:
    """Phases b-f: build, closed loop, open loop, finite logits, report.

    Raises on the first failed check; returns what the phases measured."""
    times: dict = {}
    ec = EngineConfig(max_batch=N_REQUESTS, cache_len=CACHE_LEN)
    gen = GenerationConfig(decoder="greedy", temperature=0.0,
                           max_new_tokens=NEW_TOKENS)

    with _Phase("build", times):
        lvlm = LVLM.from_pretrained(arch, smoke=smoke, seed=0)
        n_params = sum(int(x.size) for x in jax.tree.leaves(lvlm.params))
        jax.block_until_ready(lvlm.params)
    cfg = lvlm.cfg
    print(f"model {cfg.name}: {n_params} parameters, {cfg.num_layers} "
          f"layers, d_model {cfg.d_model}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}", flush=True)

    with _Phase("closed_loop", times):
        reqs = _requests(cfg)
        rep = lvlm.serve(reqs, engine_cfg=ec, gen=gen)
        eng = rep.engine
        _check(not eng.aborted, f"aborted: {[r.rid for r in eng.aborted]}")
        _check(sorted(r.rid for r in rep.requests)
               == sorted(r.rid for r in reqs),
               f"finished {[r.rid for r in rep.requests]} of "
               f"{[r.rid for r in reqs]}")
        for r in reqs:
            _check(r.state.name == "DONE" and not r.aborted,
                   f"request {r.rid} ended {r.state.name}")
            _check(len(r.generated) == NEW_TOKENS,
                   f"request {r.rid} has {len(r.generated)} tokens")
            _check(all(0 <= t < cfg.vocab_size for t in r.generated),
                   f"request {r.rid} has out-of-vocabulary tokens")
            # KV holds the post-compression prompt; the reservation covers
            # it and shrinks with pruning (the engine rounds it to blocks)
            reserved = eng.kv_request_tokens(r)
            _check(reserved >= r.kv_prompt_len + NEW_TOKENS,
                   f"request {r.rid} reserved {reserved} < "
                   f"{r.kv_prompt_len + NEW_TOKENS}")
            pruned = r.kv_prompt_len < r.prompt_len
            _check(pruned == (r.compression != "none"),
                   f"{r.compression} request {r.rid} holds "
                   f"{r.kv_prompt_len} of {r.prompt_len} prompt tokens")
        closed = {r.rid: list(r.generated) for r in reqs}
        first_none = next(r for r in reqs if r.compression == "none")
        del rep, eng

    with _Phase("open_loop", times):
        reqs = _requests(cfg)
        arrivals = np.cumsum(np.random.RandomState(0).exponential(
            1.0 / OPEN_LOOP_RATE, size=len(reqs)))
        for r, t in zip(reqs, arrivals):
            r.arrival = float(t)
        server = lvlm.serve_async(ec, gen=gen)

        async def drive():
            async with server:
                toks = await asyncio.gather(
                    *(_consume(server.submit(r)) for r in reqs))
            return toks, server.summary()

        toks, summary = asyncio.run(drive())
        opened = {r.rid: t for r, t in zip(reqs, toks)}
        _check(opened == closed,
               f"serve_async tokens {opened} != serve tokens {closed}")
        _check(summary["finished"] == len(reqs)
               and summary["aborted"] == 0
               and summary["tokens"] == len(reqs) * NEW_TOKENS,
               f"open-loop summary: finished {summary['finished']}, "
               f"aborted {summary['aborted']}, tokens {summary['tokens']}")
        del server

    with _Phase("finite_logits", times):
        batch = {"tokens": jnp.asarray([first_none.tokens], jnp.int32),
                 "visual_embeds": jnp.asarray(first_none.visual_embeds)[None]}
        prefill = jax.jit(partial(lvlm.model.prefill, cache_len=CACHE_LEN,
                                  windowed=False))
        logits, _ = prefill(lvlm.params, batch)
        want = (1, first_none.prompt_len, cfg.vocab_size)
        _check(logits.shape == want, f"logits {logits.shape} != {want}")
        _check(bool(jnp.isfinite(logits).all()), "non-finite logits")
        top = int(jnp.argmax(logits[0, -1]))
        _check(top == closed[first_none.rid][0],
               f"prefill argmax {top} != first served token "
               f"{closed[first_none.rid][0]}")

    with _Phase("report", times):
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: "
          f"{peak if peak is not None else 'not reported by the backend'}",
          flush=True)
    return {"n_params": n_params, "tokens": closed,
            "peak_bytes_in_use": peak, "seconds": times}


def main() -> int:
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"phase device: {time.perf_counter() - t0} s on {_device_line()}"
          f"; bytes_limit {stats.get('bytes_limit')}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    run_phases(ARCH, smoke=False)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
