"""Engine step: wall self time of a prefill call (ms).

The ``prefill_forward`` profiler site less its nested sites, over its
count, in the window. The site ends after the first token is pulled to
the host, so this is dispatch and device time together. A prefill runs
inside the engine step every running client waits on, so it sets the
gaps that hold a prefill: ``itl_p95_ms``."""
LAYER = "engine step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
SITE = "prefill_forward"


def read(ctx):
    a = ctx.start["sites"].get(SITE, {"count": 0, "wall_self_s": 0.0})
    b = ctx.end["sites"].get(SITE)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["wall_self_s"] - a["wall_self_s"]) * 1e3 \
        / (b["count"] - a["count"])
