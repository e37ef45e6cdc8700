"""Continuous hot-path profiling: streaming per-site time histograms.

Where ``repro.obs.trace`` answers "what happened to request N" (lifecycle
spans on the virtual clock), this module answers "where does an engine
step actually spend its wall time" -- continuously, in production, with
the same zero-overhead-when-off discipline:

  * ``NULL_PROFILER`` (a ``NullProfiler``) is the default everywhere; its
    ``enabled`` class attribute is ``False`` and every hot-path site guards
    on it (``if profiler.enabled:``), so the unprofiled path makes ZERO
    profiler calls (locked by a patch-the-null-profiler-to-raise test,
    mirroring the NullTracer test).
  * ``Profiler`` accumulates streaming log2-bucket histograms of wall time
    (``time.perf_counter``) per named *site* -- engine step and its phases,
    prefill forward, per-decoder-group decode launch, compression,
    KV-migration transfer, prefix-tier probe/install.
  * Sites nest (``compress`` runs inside ``prefill_forward``), and the
    profiler attributes wall time both ways: *total* (site entry to exit)
    and *self* (total minus enclosed child sites). Nesting paths feed the
    collapsed-stack (flamegraph-compatible) export.
  * *Stackless* durations sit beside the stack: ``wait_begin``/``wait_end``
    time a wait inside one function (the host blocked on a device result)
    without taking it out of the enclosing site's self time, and
    ``interval_begin``/``interval_end`` time a span that crosses functions
    or event-loop turns (a request's queue wait, the pump's host time
    between steps), keyed so that many can be open at once.
  * ``count`` keeps event counters (decode rows per launch) beside them.
  * While a ``jax.profiler`` trace is being recorded, every site and
    duration is also a ``TraceAnnotation`` named ``repro:<site>`` whose
    stats are the keyword arguments of its begin call, so the spans sit on
    the host plane of the same trace as the device's operations.

Profiling only ever READS clocks -- it never touches the PRNG key, the
scheduler, or the virtual clock -- so profiled runs stay bit-identical at
temperature 0 (locked by test).

Exports: ``profile_families`` renders Prometheus families into a
``PromText`` (picked up by ``metrics_snapshot()``), ``Profiler.write_json``
feeds ``scripts/profile_report.py`` (table + collapsed stacks), and
``Profiler.bench_record`` is the schema-v1 block embedded in
``--emit-bench`` records for ``repro.obs.regress`` to gate on.
"""
from __future__ import annotations

import json
import math
import time
from typing import Callable, Dict, Hashable, List, Optional, Tuple

# log2 histogram upper bounds in seconds: 1us * 2**i -- 30 buckets cover
# 1us .. ~537s, far beyond any single hot-path site on any hardware
_BUCKET_BASE = 1e-6
_NUM_BUCKETS = 30


def bucket_bounds() -> List[float]:
    """The histogram's upper bounds in seconds (shared by all sites)."""
    return [_BUCKET_BASE * (1 << i) for i in range(_NUM_BUCKETS)]


def _bucket_index(x: float) -> int:
    if x <= _BUCKET_BASE:
        return 0
    i = int(math.ceil(math.log2(x / _BUCKET_BASE)))
    return min(max(i, 0), _NUM_BUCKETS - 1)


class NullProfiler:
    """Disabled profiler: every method is a no-op and ``enabled`` is a
    class attribute so the hot-path guard is one attribute load. Sites
    must NEVER call these when profiling is off -- guard with
    ``if profiler.enabled:`` (rule O003 checks site and wait pairing; the
    patch-to-raise test checks the guards)."""

    enabled = False

    def site_begin(self, site: str, **stats) -> None:
        pass

    def site_end(self, site: str) -> None:
        pass

    def site_drop(self, site: str) -> None:
        pass

    def wait_begin(self, site: str, **stats) -> None:
        pass

    def wait_end(self, site: str) -> None:
        pass

    def interval_begin(self, site: str, key: Hashable, **stats) -> None:
        pass

    def interval_end(self, site: str, key: Hashable) -> None:
        pass

    def interval_drop(self, site: str, key: Hashable) -> None:
        pass

    def count(self, name: str, n: float = 1) -> None:
        pass

    # read-side surface (safe on the null profiler: empty results)
    def snapshot(self) -> Dict[str, Dict]:
        return {}

    def collapsed(self) -> List[str]:
        return []

    def bench_record(self) -> Dict:
        return {"schema_version": 1, "sites": {}, "counters": {}}


NULL_PROFILER = NullProfiler()


class _Site:
    __slots__ = ("count", "wall_total", "wall_self", "wall_counts",
                 "stackless")

    def __init__(self, stackless: bool) -> None:
        self.count = 0
        self.wall_total = 0.0
        self.wall_self = 0.0
        self.wall_counts = [0] * _NUM_BUCKETS
        self.stackless = stackless

    def add(self, total: float, self_w: float) -> None:
        self.count += 1
        self.wall_total += total
        self.wall_self += self_w
        self.wall_counts[_bucket_index(total)] += 1


def _trim_buckets(counts: List[int]) -> List[List[float]]:
    """[(upper_bound_s, count), ...] up to the last non-empty bucket --
    cumulative rendering stays exact (all trimmed buckets are zero)."""
    last = -1
    for i, c in enumerate(counts):
        if c:
            last = i
    bounds = bucket_bounds()
    return [[bounds[i], counts[i]] for i in range(last + 1)]


def _leave(ann) -> None:
    if ann is not None:
        ann.__exit__(None, None, None)


class Profiler(NullProfiler):
    """Enabled profiler: streaming log-bucket histograms per site.

    One instance is shared by a whole fleet (like the Tracer): engine
    steps are synchronous, so begin/end pairs never interleave across
    replicas and a single site stack is sufficient for self/total
    attribution.
    """

    enabled = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self._clock = clock
        self._sites: Dict[str, _Site] = {}
        # counter name -> [events, sum of the amounts counted]
        self._counters: Dict[str, List[float]] = {}
        # open-site stack: [site, t0, child_wall_total, annotation] frames
        self._stack: List[List] = []
        # open stackless durations: (site, key) -> (t0, annotation)
        self._open: Dict[Tuple[str, Hashable], Tuple[float, object]] = {}
        # collapsed stacks: "outer;inner" -> self wall seconds
        self._paths: Dict[str, float] = {}

    def _enter(self, site: str, stats: Dict):
        """The site's trace span, entered; None while no trace records
        (checking that costs far less than building an annotation)."""
        if not self._annotation.is_enabled():
            return None
        ann = self._annotation("repro:" + site, **stats)
        ann.__enter__()
        return ann

    def _record(self, site: str, total: float, self_w: float,
                stackless: bool) -> None:
        rec = self._sites.get(site)
        if rec is None:
            rec = self._sites[site] = _Site(stackless)
        rec.add(total, self_w)

    def _unwind(self, site: str) -> Optional[List]:
        """Pop to the frame of ``site`` (defensive: a site that leaked an
        inner begin is discarded rather than corrupting attribution)."""
        while self._stack:
            top = self._stack.pop()
            if top[0] == site:
                return top
            _leave(top[3])
        return None

    # ------------------------------------------------------ recording --
    def site_begin(self, site: str, **stats) -> None:
        ann = self._enter(site, stats)
        self._stack.append([site, self._clock(), 0.0, ann])

    def site_end(self, site: str) -> None:
        frame = self._unwind(site)
        if frame is None:
            return
        total = self._clock() - frame[1]
        _leave(frame[3])
        self_w = max(total - frame[2], 0.0)
        if self._stack:
            self._stack[-1][2] += total
            path = ";".join(f[0] for f in self._stack) + ";" + site
        else:
            path = site
        self._record(site, total, self_w, stackless=False)
        self._paths[path] = self._paths.get(path, 0.0) + self_w

    def site_drop(self, site: str) -> None:
        """Close a site without recording it (a call that did no work);
        its time stays in the enclosing site's self time."""
        frame = self._unwind(site)
        if frame is not None:
            _leave(frame[3])

    def _pop(self, site: str, key: Hashable) -> Optional[float]:
        """Close an open stackless duration's span; its start, or None."""
        entry = self._open.pop((site, key), None)
        if entry is None:
            return None
        _leave(entry[1])
        return entry[0]

    def wait_begin(self, site: str, **stats) -> None:
        """Open a stackless duration inside one function: the enclosing
        site's self time keeps it. A wait left open by a failure is
        replaced by the next begin."""
        self._pop(site, None)
        ann = self._enter(site, stats)
        self._open[(site, None)] = (self._clock(), ann)

    def wait_end(self, site: str) -> None:
        self.interval_end(site, None)

    def interval_begin(self, site: str, key: Hashable, **stats) -> None:
        """Open a stackless duration that may end in another function or
        event-loop turn, one per ``key``; an interval already open under
        the key keeps its start."""
        if (site, key) not in self._open:
            ann = self._enter(site, stats)
            self._open[(site, key)] = (self._clock(), ann)

    def interval_end(self, site: str, key: Hashable) -> None:
        t1 = self._clock()
        t0 = self._pop(site, key)
        if t0 is not None:
            self._record(site, t1 - t0, t1 - t0, stackless=True)

    def interval_drop(self, site: str, key: Hashable) -> None:
        """Forget an open interval without recording it (the request
        left before it ended)."""
        self._pop(site, key)

    def count(self, name: str, n: float = 1) -> None:
        c = self._counters.get(name)
        if c is None:
            c = self._counters[name] = [0, 0]
        c[0] += 1
        c[1] += n

    # ------------------------------------------------------- exports --
    def snapshot(self) -> Dict[str, Dict]:
        """Per-site accumulators (counts, wall self/total, whether the
        site is stackless, trimmed (upper_bound_s, count) histogram
        buckets) and, under their own names, counters (``count`` events
        summing to ``total``)."""
        out: Dict[str, Dict] = {}
        for site, s in self._sites.items():
            out[site] = {
                "count": s.count,
                "wall_total_s": s.wall_total,
                "wall_self_s": s.wall_self,
                "stackless": s.stackless,
                "wall_buckets": _trim_buckets(s.wall_counts),
            }
        for name, (events, total) in self._counters.items():
            out[name] = {"count": events, "total": total}
        return dict(sorted(out.items()))

    def collapsed(self) -> List[str]:
        """Collapsed-stack lines (``outer;inner <self_usec>``) -- feed to
        any flamegraph renderer (e.g. flamegraph.pl, speedscope)."""
        return [f"{path} {max(1, int(round(us * 1e6)))}"
                for path, us in sorted(self._paths.items())]

    def bench_record(self) -> Dict:
        """The schema-v1 profile block for ``--emit-bench`` records:
        scalar per-site attribution and counters only (histograms stay in
        ``write_json``; bench records are for regression gating)."""
        sites = {site: {"count": s.count, "wall_total_s": s.wall_total,
                        "wall_self_s": s.wall_self}
                 for site, s in sorted(self._sites.items())}
        counters = {name: {"count": c[0], "total": c[1]}
                    for name, c in sorted(self._counters.items())}
        return {"schema_version": 1, "sites": sites, "counters": counters}

    def write_json(self, path: str) -> None:
        """Full profile document for ``scripts/profile_report.py``."""
        doc = {
            "schema_version": 1,
            "kind": "profile",
            "sites": self.snapshot(),
            "collapsed": {p: v for p, v in sorted(self._paths.items())},
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")


def profile_families(prom, profiler, *,
                     labels: Optional[Dict[str, str]] = None) -> None:
    """Render a profiler into a ``PromText``: the
    ``repro_profile_wall_seconds`` histogram per site, self-time counters
    for stacked sites (labeled by ``site``), and event and amount
    counters per profiler counter (labeled by ``counter``)."""
    for name, s in profiler.snapshot().items():
        if "wall_total_s" not in s:
            lab = dict(labels or {}, counter=name)
            prom.counter("profile_events_total",
                         "Events at a hot-path counter.", s["count"],
                         labels=lab)
            prom.counter("profile_counted_total",
                         "Sum of the amounts a hot-path counter counted.",
                         s["total"], labels=lab)
            continue
        lab = dict(labels or {}, site=name)
        prom.histogram(
            "profile_wall_seconds",
            "Wall time per hot-path site call (log2 buckets).",
            s["wall_buckets"], s["wall_total_s"], s["count"], labels=lab)
        if not s["stackless"]:
            prom.counter(
                "profile_wall_self_seconds_total",
                "Cumulative self wall time (enclosed child sites "
                "excluded).", s["wall_self_s"], labels=lab)
