"""Programs on the device: the decode step's share of its roofline (%).

The least time of every decode step made while tracing (``flops``: the
weights, the key/value entries of the live positions and the head for
the live rows) over the device time of that program's runs in the
trace. Silent when the calls and the runs do not pair up."""
LAYER = "device programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p50_ms"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.probes.decode:
        return None
    runs = ctx.trace_reduce.program_seconds(ctx.trace).get(
        ctx.modules.get("decode"))
    if runs is None or runs[0] != len(ctx.probes.decode) or runs[1] <= 0:
        return None
    least = sum(ctx.flops.least_time(*ctx.flops.decode_cost(
        ctx.model, live), ctx.peaks)
        for live in ctx.probes.decode if live)
    return 100.0 * least / runs[1]
