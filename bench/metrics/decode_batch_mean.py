"""Mean live rows per decode-only run in the window."""
LAYER, UNIT, SOURCE, MOVES = "scheduler", "rows", "host_clock", \
    "sla_attainment"


def read(ctx):
    from bench.harness import runs_between
    runs = [r for r in runs_between(ctx.res.timeline, ctx.res.open,
                                    ctx.res.close)
            if r.cycles and not r.prefill_tokens]
    if not runs:
        return None
    return sum(r.rows for r in runs) / len(runs)
