"""Compression: wall time of one visual-token compression call (ms),
the ``compress`` profiler site's total over its count in the window. It
runs inside the engine step before the prefill, so it lengthens the
gaps that hold a prefill: ``itl_p95_ms``."""
LAYER = "compression"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"
SITE = "compress"


def read(ctx):
    a = ctx.start["sites"].get(SITE, {"count": 0, "wall_total_s": 0.0})
    b = ctx.end["sites"].get(SITE)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["wall_total_s"] - a["wall_total_s"]) * 1e3 \
        / (b["count"] - a["count"])
