"""Device: share of the traced window with no operation running (%)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p50_ms"


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    busy = ctx.trace_reduce.busy_seconds(ctx.trace)
    return 100.0 * (1.0 - busy / (hi - lo))
