"""From a JAX profiler trace (``.xplane.pb``) to device busy time,
per-program device time and the longest idle gaps.

``load(path)`` reads the file with ``jax.profiler.ProfileData``; the rest
works on plain tuples, so the arithmetic can be checked without a trace.

Each TPU's device plane (``/device:TPU:<n>``) has an ``XLA Ops`` line
(one event per executed operation) and an ``XLA Modules`` line (one event
per executed program); a trace without such a plane is refused. Host
spans are the ``TraceAnnotation`` events the harness writes around its
calls into the engine; the traced window is the span named ``WINDOW``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench:trace_window"
HOST_PREFIX = "bench:"
OUTSIDE = "outside the engine (event loop, clients)"

Interval = Tuple[float, float]          # (start_s, end_s)


@dataclasses.dataclass
class Trace:
    window: Interval
    ops: List[Tuple[str, float, float]]        # (name, start_s, end_s)
    programs: List[Tuple[str, float, float]]   # (module, start_s, end_s)
    host: List[Tuple[str, float, float]]       # harness spans
    n_devices: int


def _suffixless(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``: drop the run or program id."""
    return re.sub(r"\(\d+\)$", "", name)


def find_xplane(log_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith("/device:TPU"))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane; planes are "
                         f"{[p.name for p in pd.planes]}")
    ops, programs, host = [], [], []
    window = None
    for plane in pd.planes:
        on_device = plane.name in devices
        for line in plane.lines:
            for e in line.events:
                t0 = e.start_ns * 1e-9
                t1 = t0 + e.duration_ns * 1e-9
                if on_device:
                    if line.name == "XLA Ops":
                        ops.append((e.name, t0, t1))
                    elif line.name == "XLA Modules":
                        programs.append((_suffixless(e.name), t0, t1))
                elif e.name == WINDOW:
                    window = (t0, t1)
                elif e.name.startswith(HOST_PREFIX):
                    host.append((e.name[len(HOST_PREFIX):], t0, t1))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} span")
    return Trace(window, ops, programs, host, len(devices))


def clip(events, window: Interval):
    lo, hi = window
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def busy_seconds(tr: Trace) -> float:
    """Seconds in the window in which some operation ran, averaged over
    the devices traced."""
    ops = clip(tr.ops, tr.window)
    return sum(b - a for a, b in union([(a, b) for _, a, b in ops])) \
        / tr.n_devices


def program_seconds(tr: Trace) -> Dict[str, Tuple[int, float]]:
    """module -> (runs, device seconds) for runs inside the window."""
    out: Dict[str, List] = {}
    lo, hi = tr.window
    for name, a, b in tr.programs:
        if a >= lo and b <= hi:
            rec = out.setdefault(name, [0, 0.0])
            rec[0] += 1
            rec[1] += b - a
    return {k: (v[0], v[1]) for k, v in out.items()}


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: the
    operation's own name, without the instruction text XLA prints."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time, each named
    ``<program>/<operation>`` by the program run that holds it."""
    runs = sorted(tr.programs, key=lambda r: r[1])
    starts = [r[1] for r in runs]
    tot: Dict[str, float] = {}
    for name, a, b in clip(tr.ops, tr.window):
        i = bisect.bisect_right(starts, a) - 1
        prog = runs[i][0] if i >= 0 and runs[i][2] >= a else None
        key = op_name(name)
        if prog and not key.startswith(prog + "/"):
            key = f"{prog}/{key}"
        tot[key] = tot.get(key, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest stretches with no operation on the device, each
    named by what the host was doing then (``_host_activity``)."""
    busy = union([(a, b) for _, a, b in clip(tr.ops, tr.window)])
    lo, hi = tr.window
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:n]:
        out.append([_host_activity(tr.host, a, b), b - a])
    return out


def _host_activity(host, a: float, b: float) -> str:
    """Name of the innermost (shortest) harness span that covers at least
    half of [a, b]; failing that, the span with the most overlap."""
    covering, best, best_ov = [], OUTSIDE, 0.0
    for name, s, e in host:
        ov = min(b, e) - max(a, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * (b - a):
            covering.append((e - s, name))
        if ov > best_ov:
            best, best_ov = name, ov
    return min(covering)[1] if covering else best
