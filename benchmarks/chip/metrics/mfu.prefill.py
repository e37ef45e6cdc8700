"""Model: prefill operations the delivered tokens needed, over the
window and the chip's bf16 peak (%).

Counts useful work only (``flops.window_ops``): a first token its
prompt's prefill with the last position unembedded, a later token one
decode row at its context. Whatever program computes it, this share
bounds the rooflines of the kernels on the path."""
LAYER = "model"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(ctx):
    if ctx.peaks is None:
        return None
    pre, _ = ctx.flops.window_ops(
        ctx.model, ctx.visual_kept, ctx.records,
        ctx.t0, ctx.t_close)
    ops = pre
    if not ops:
        return None
    return 100.0 * ops / ctx.seconds / ctx.peaks["bf16_flops_per_s"]
