"""Scheduler: decode tokens per decode iteration in the window.

Tokens delivered in the window that were not a request's first token,
over the engine's decode launches (the ``decode:<group>`` profiler
sites) in the window."""
LAYER = "scheduler"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "output_tokens_per_s"


def _decode_launches(snap):
    return sum(s["count"] for k, s in snap["sites"].items()
               if k.startswith("decode:"))


def read(ctx):
    launches = _decode_launches(ctx.end) - _decode_launches(ctx.start)
    decoded = sum(sum(ctx.t0 <= t <= ctx.t_close for t in r.times[1:])
                  for r in ctx.records)
    return decoded / launches if launches else None
