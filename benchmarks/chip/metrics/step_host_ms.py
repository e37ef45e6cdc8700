"""Engine step: host time of a step in which the host was not blocked on
the device (ms). The ``engine_step`` profiler site's total, less the
totals of the waits on a device result inside it (``wait:decode``,
``wait:prefill``, ``wait:compress``), over the steps in the window. The
device idles through much of it, so it sets the gaps: ``itl_p50_ms``."""
LAYER = "engine step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p50_ms"
SITE = "engine_step"
WAITS = ("wait:decode", "wait:prefill", "wait:compress")


def _total(snap, site):
    return snap["sites"].get(site, {}).get("wall_total_s", 0.0)


def read(ctx):
    a = ctx.start["sites"].get(SITE, {"count": 0})
    b = ctx.end["sites"].get(SITE)
    if b is None or b["count"] == a["count"]:
        return None
    waits = sum(_total(ctx.end, w) - _total(ctx.start, w) for w in WAITS)
    host = _total(ctx.end, SITE) - _total(ctx.start, SITE) - waits
    return host * 1e3 / (b["count"] - a["count"])
