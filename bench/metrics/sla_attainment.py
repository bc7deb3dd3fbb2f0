"""Share of requests due in the window that finished within their own
deadline, counted from due time. Failed, shed, rejected and unfinished
requests are misses."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "%", "host_clock", None


def read(ctx):
    from bench.harness import met_deadline, window_requests
    reqs = window_requests(ctx.res)
    if not reqs:
        return None
    return 100.0 * sum(met_deadline(ctx.res, r) for r in reqs) / len(reqs)
