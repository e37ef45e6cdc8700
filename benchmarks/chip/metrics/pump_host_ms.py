"""Async server: wall time from one engine step's return to the pump's
next call of it (ms), the ``pump_host`` profiler duration's total over
its count in the window: fanning tokens out to the streams, admission,
and the clients' turn on the event loop, with the device idle unless
work is still queued. It adds to every gap: ``itl_p50_ms``."""
LAYER = "async server"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p50_ms"
SITE = "pump_host"


def read(ctx):
    a = ctx.start["sites"].get(SITE, {"count": 0, "wall_total_s": 0.0})
    b = ctx.end["sites"].get(SITE)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["wall_total_s"] - a["wall_total_s"]) * 1e3 \
        / (b["count"] - a["count"])
