"""Hot-path site report over a ``Profiler.write_json`` document.

    prof = Profiler()
    lvlm.serve_cluster(..., profile=prof)
    ...
    prof.write_json("profile.json")
    PYTHONPATH=src python scripts/profile_report.py profile.json \
        --collapsed profile.folded

Prints a per-site table -- call count, wall total/self seconds, self
share of the stacked sites' self time -- sorted by self wall time (where
an optimization pays off first), with stackless durations (device waits,
queue wait, pump host time) and counters listed after it, and optionally
writes the collapsed-stack lines (``outer;inner <usec>``) any flamegraph
renderer consumes (flamegraph.pl, speedscope, inferno).
"""
from __future__ import annotations

import argparse
import json
import sys


def load_profile(path):
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    if "sites" not in doc:
        raise ValueError(f"{path}: not a profile document (no 'sites')")
    return doc


def report(doc, out=sys.stdout) -> int:
    entries = doc.get("sites", {})
    sites = {k: s for k, s in entries.items() if "wall_total_s" in s}
    if not sites:
        print("no profiled sites in the document", file=out)
        return 1
    stacked = {k: s for k, s in sites.items() if not s.get("stackless")}
    total_self = sum(s["wall_self_s"] for s in stacked.values()) or 1.0
    print(f"profile_report: {len(sites)} site(s), "
          f"{sum(s['count'] for s in sites.values())} calls, "
          f"{total_self:.6f}s self wall", file=out)
    print(f"{'site':>22} {'count':>7} {'wall_total_s':>13} "
          f"{'wall_self_s':>12} {'self%':>7}", file=out)
    order = sorted(sites.items(),
                   key=lambda kv: (bool(kv[1].get("stackless")),
                                   -kv[1]["wall_self_s"]))
    for name, s in order:
        share = "-" if s.get("stackless") \
            else f"{s['wall_self_s'] / total_self:.1%}"
        print(f"{name:>22} {s['count']:>7} {s['wall_total_s']:>13.6f} "
              f"{s['wall_self_s']:>12.6f} {share:>7}", file=out)
    for name, c in sorted(entries.items()):
        if name not in sites:
            print(f"{name:>22} {c['count']:>7} events, total "
                  f"{c['total']:g}", file=out)
    return 0


def write_collapsed(doc, path) -> int:
    """Collapsed-stack lines from the document's ``collapsed`` map
    (path -> self seconds), in integer microseconds."""
    collapsed = doc.get("collapsed", {})
    n = 0
    with open(path, "w", encoding="utf-8") as f:
        for stack, secs in sorted(collapsed.items()):
            f.write(f"{stack} {max(1, int(round(secs * 1e6)))}\n")
            n += 1
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("profile", help="JSON written by Profiler.write_json")
    ap.add_argument("--collapsed", metavar="PATH",
                    help="also write flamegraph-compatible collapsed "
                         "stacks to PATH")
    args = ap.parse_args(argv)
    doc = load_profile(args.profile)
    rc = report(doc)
    if args.collapsed:
        n = write_collapsed(doc, args.collapsed)
        print(f"wrote {n} collapsed stack(s) to {args.collapsed}")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
