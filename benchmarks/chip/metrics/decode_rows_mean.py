"""Scheduler: rows a decode launch carries, the ``decode_rows`` profiler
counter's total over its count in the window. Counted where the launch
is made, beside ``decode_batch_mean``, which counts tokens at the
clients."""
LAYER = "scheduler"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "output_tokens_per_s"
COUNTER = "decode_rows"


def read(ctx):
    a = ctx.start["sites"].get(COUNTER, {"count": 0, "total": 0})
    b = ctx.end["sites"].get(COUNTER)
    if b is None or b["count"] == a["count"]:
        return None
    return (b["total"] - a["total"]) / (b["count"] - a["count"])
