"""Programs on the device: the prefill program's share of its roofline (%).

The least time of every prefill call made while tracing (``flops``:
operations and bytes the prompt needs, the larger bound) over the device
time of that program's runs in the trace. Silent when the calls and the
runs do not pair up, or the program is not found by its module name."""
LAYER = "device programs"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.probes.prefill:
        return None
    runs = ctx.trace_reduce.program_seconds(ctx.trace).get(
        ctx.modules.get("prefill"))
    if runs is None or runs[0] != len(ctx.probes.prefill) or runs[1] <= 0:
        return None
    least = sum(ctx.flops.least_time(*ctx.flops.prefill_cost(
        ctx.model, n_text, nv), ctx.peaks)
        for n_text, nv in ctx.probes.prefill)
    return 100.0 * least / runs[1]
