"""Tail of time per output token: the 95th percentile, over requests due
in the window with two or more output tokens, of (last token - first
token) / (tokens - 1). Per-layer for the same reason as
``tail.ttft_p95_ms``."""
LAYER, UNIT, SOURCE, MOVES = "client", "ms", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import percentile, tpot_values
    v = percentile(tpot_values(ctx.res), 95)
    return None if v is None else v * 1e3
