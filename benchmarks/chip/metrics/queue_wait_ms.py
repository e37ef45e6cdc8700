"""Scheduler: wall time from a request's admission to the start of its
first prefill chunk (ms), the ``queue_wait`` profiler duration's total
over its count in the window. Under load most of a first token's time is
this wait: ``ttft_p90_ms``."""
LAYER = "scheduler"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_p90_ms"
SITE = "queue_wait"


def read(ctx):
    a = ctx.start["sites"].get(SITE, {"count": 0, "wall_total_s": 0.0})
    b = ctx.end["sites"].get(SITE)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["wall_total_s"] - a["wall_total_s"]) * 1e3 \
        / (b["count"] - a["count"])
