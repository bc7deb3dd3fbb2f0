"""Output tokens delivered to clients inside the window, over the
window's seconds (tokens of every request, wherever it was due)."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "tokens/s", "host_clock", None


def read(ctx):
    res = ctx.res
    n = sum(1 for r in res.reqs for t in r.token_times
            if res.open <= t < res.close)
    return n / (res.close - res.open)
