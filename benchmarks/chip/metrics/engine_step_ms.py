"""Engine step: the window over the engine iterations run in it (ms)."""
LAYER = "engine step"
UNIT = "ms"
SOURCE = "program_counter"
MOVES = "itl_p50_ms"


def read(ctx):
    iters = ctx.end["iters"] - ctx.start["iters"]
    return (ctx.end["t"] - ctx.start["t"]) * 1e3 / iters if iters else None
