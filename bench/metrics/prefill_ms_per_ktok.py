"""Wall time of the runs in the window that prefill prompts, per 1,000
prompt tokens they prefilled."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import runs_between
    runs = [r for r in runs_between(ctx.res.timeline, ctx.res.open,
                                    ctx.res.close) if r.prefill_tokens]
    toks = sum(r.prefill_tokens for r in runs)
    if not toks:
        return None
    return 1e6 * sum(r.t1 - r.t0 for r in runs) / toks
