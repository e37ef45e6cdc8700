"""Engine step: programs compiled or fetched from the compile cache
during the window (``jax.monitoring`` events). Warm-up should leave
none; each one stalls a step."""
LAYER = "engine step"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx.end["compiles"] - ctx.start["compiles"]
