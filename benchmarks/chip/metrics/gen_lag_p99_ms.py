"""Load generator: 99th percentile of send time minus due time (ms).

A late generator delays every request it sends; read beside
``ttft_p90_ms`` so a starved client is not taken for a fast server."""
LAYER = "load generator"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "ttft_p90_ms"


def read(ctx):
    lags = [(r.sent - r.due) * 1e3 for r in ctx.records if r.sent is not None]
    return ctx.stats.percentile(lags, 99) if lags else None
