"""Share of the prefill positions dispatched in the window that are
padding: 100 x (1 - real prompt tokens / batch x length buckets), from
the program's ``prefill_tokens`` and ``prefill_positions`` counters."""
LAYER, UNIT, SOURCE, MOVES = "engine", "%", "host_clock", "sla_attainment"


def read(ctx):
    rec = getattr(ctx, "spans", None)
    if rec is None:
        return None
    n = {"prefill_tokens": 0, "prefill_positions": 0}
    for name, t, k in rec.counters:
        if name in n and ctx.res.open <= t < ctx.res.close:
            n[name] += k
    if not n["prefill_positions"]:
        return None
    return 100.0 * (1.0 - n["prefill_tokens"] / n["prefill_positions"])
