"""Per-stage attribution report over a repro.obs JSONL event log.

    PYTHONPATH=src python -m repro.launch.serve --smoke \
        --roles prefill,decode \
        --open-loop 2000 --trace-events /tmp/events.jsonl
    PYTHONPATH=src python scripts/trace_report.py /tmp/events.jsonl

For every request the report splits its lifetime (virtual clock) into
the lifecycle stages the tracer spans mark -- admission wait, prefill,
compression, KV migration, decode (the remainder) -- then aggregates
mean/p50/p95 per stage plus the share of total request-seconds each
stage consumed. That attribution is the first question a latency
regression asks: did the time go to the admission gate, the chunked
prefill, the KV link, or the decode loop?

Also reports per-replica engine occupancy from the ``engine_step``
slices and the wall/virtual clock ratio (how much real time the smoke
model spends per modeled second).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

# stage span names, innermost attribution order; "decode" is the
# request-span remainder after the named stages
STAGES = ("admission_wait", "prefill", "compress", "kv_migration")


def load_events(path):
    events = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def _pct(vals, p):
    if not vals:
        return 0.0
    vals = sorted(vals)
    idx = min(len(vals) - 1, int(round((p / 100.0) * (len(vals) - 1))))
    return vals[idx]


def attribute(events):
    """Per-rid stage durations (virtual seconds) from span pairs."""
    opens = {}                       # (rid, name) -> begin event
    stages = defaultdict(lambda: defaultdict(float))   # rid -> stage -> s
    request = {}                     # rid -> (begin_vt, end_vt, aborted)
    for ev in events:
        k, name, rid = ev.get("k"), ev.get("name"), ev.get("rid")
        if k == "B":
            opens[(rid, name)] = ev
        elif k == "E":
            b = opens.pop((rid, name), None)
            if b is None:
                continue
            dur = ev.get("vt", 0.0) - b.get("vt", 0.0)
            if name == "request":
                aborted = bool((ev.get("attrs") or {}).get("aborted"))
                request[rid] = (b.get("vt", 0.0), ev.get("vt", 0.0),
                                aborted)
            elif name in STAGES:
                stages[rid][name] += dur
    return request, stages


def occupancy(events):
    """Per-replica engine busy fraction: sum of engine_step slice
    durations over that replica's traced span of virtual time."""
    busy = defaultdict(float)
    lo, hi = {}, {}
    for ev in events:
        rep = ev.get("rep", 0)
        vt = ev.get("vt")
        if vt is not None:
            lo[rep] = min(lo.get(rep, vt), vt)
            hi[rep] = max(hi.get(rep, vt), vt)
        if ev.get("k") == "X" and ev.get("name") == "engine_step":
            busy[rep] += ev.get("dur", 0.0)
    return {rep: (busy[rep] / (hi[rep] - lo[rep])
                  if hi.get(rep, 0) > lo.get(rep, 0) else 0.0)
            for rep in sorted(set(lo) | set(busy))}


def _aggregate(events):
    """Shared stage aggregation: (n, aborted, totals, lifetimes) or None
    when the log holds no closed request spans."""
    request, stages = attribute(events)
    if not request:
        return None
    totals = defaultdict(list)       # stage -> per-request seconds
    lifetimes = []
    for rid, (b, e, _aborted) in sorted(request.items()):
        life = e - b
        lifetimes.append(life)
        named = 0.0
        for st in STAGES:
            s = stages[rid].get(st, 0.0)
            totals[st].append(s)
            named += s
        totals["decode"].append(max(0.0, life - named))
    aborted = sum(1 for _, (_, _, a) in request.items() if a)
    return len(lifetimes), aborted, totals, lifetimes


def _wall_virtual_ratio(events):
    wall = [ev["wt"] for ev in events if ev.get("wt") is not None]
    vts = [ev["vt"] for ev in events if ev.get("vt") is not None]
    if wall and vts and max(vts) > min(vts):
        return (max(wall) - min(wall)) / (max(vts) - min(vts))
    return None


def report_json(events):
    """Machine-readable stage-share attribution (``--json``): the same
    aggregation as the table, shaped so ``python -m repro.obs.regress``
    can diff two traced runs (``*_s`` leaves gate, ``share`` does not)."""
    agg = _aggregate(events)
    if agg is None:
        return None
    n, aborted, totals, lifetimes = agg
    grand = sum(lifetimes) or 1.0
    doc = {
        "schema_version": 1,
        "requests": n,
        "aborted": aborted,
        "events": len(events),
        "stages": {},
        "lifetime": {"mean_s": sum(lifetimes) / n,
                     "p50_s": _pct(lifetimes, 50),
                     "p95_s": _pct(lifetimes, 95)},
        "occupancy": {str(rep): frac
                      for rep, frac in occupancy(events).items()},
        "wall_virtual_ratio": _wall_virtual_ratio(events),
    }
    for st in STAGES + ("decode",):
        vals = totals[st]
        doc["stages"][st] = {"mean_s": sum(vals) / n,
                             "p50_s": _pct(vals, 50),
                             "p95_s": _pct(vals, 95),
                             "share": sum(vals) / grand}
    return doc


def report(events, out=sys.stdout):
    agg = _aggregate(events)
    if agg is None:
        print("no closed request spans in the event log", file=out)
        return 1
    n, aborted, totals, lifetimes = agg
    grand = sum(lifetimes) or 1.0
    wall = [ev["wt"] for ev in events]
    vts = [ev["vt"] for ev in events if ev.get("vt") is not None]
    print(f"trace_report: {n} request(s) ({aborted} aborted), "
          f"{len(events)} events", file=out)
    print(f"{'stage':>15} {'mean_s':>10} {'p50_s':>10} {'p95_s':>10} "
          f"{'share':>7}", file=out)
    for st in STAGES + ("decode",):
        vals = totals[st]
        share = sum(vals) / grand
        print(f"{st:>15} {sum(vals) / n:>10.6f} {_pct(vals, 50):>10.6f} "
              f"{_pct(vals, 95):>10.6f} {share:>6.1%}", file=out)
    print(f"{'lifetime':>15} {sum(lifetimes) / n:>10.6f} "
          f"{_pct(lifetimes, 50):>10.6f} {_pct(lifetimes, 95):>10.6f} "
          f"{'100.0%':>7}", file=out)
    for rep, frac in occupancy(events).items():
        print(f"replica {rep}: engine occupancy {frac:.1%}", file=out)
    if wall and vts and max(vts) > min(vts):
        ratio = (max(wall) - min(wall)) / (max(vts) - min(vts))
        print(f"wall/virtual clock ratio: {ratio:.1f}x "
              f"(wall {max(wall) - min(wall):.3f}s over virtual "
              f"{max(vts) - min(vts):.6f}s)", file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("events", help="JSONL event log (--trace-events / "
                                   "Tracer.write_jsonl / JsonlSink)")
    ap.add_argument("--json", action="store_true",
                    help="emit the attribution as JSON (diffable with "
                         "python -m repro.obs.regress)")
    args = ap.parse_args(argv)
    events = load_events(args.events)
    if args.json:
        doc = report_json(events)
        if doc is None:
            print("no closed request spans in the event log",
                  file=sys.stderr)
            return 1
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0
    return report(events)


if __name__ == "__main__":
    raise SystemExit(main())
