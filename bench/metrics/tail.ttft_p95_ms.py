"""Tail of time to first token: the 95th percentile, over all requests
due in the window, of due time to the first token at the client (a
request with no token by the end is censored there). A per-layer reading
and not an end-to-end metric: near the knee, with some tens of requests
in a window, it swings with which burst the long prompts fall into."""
LAYER, UNIT, SOURCE, MOVES = "client", "ms", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import percentile, ttft_values
    v = percentile(ttft_values(ctx.res), 95)
    return None if v is None else v * 1e3
