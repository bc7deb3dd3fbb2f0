"""Wall time of decode-only ``execute_run`` calls in the window (each
ends in the engine's own sync) over the decode cycles they ran."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import runs_between
    runs = [r for r in runs_between(ctx.res.timeline, ctx.res.open,
                                    ctx.res.close)
            if r.cycles and not r.prefill_tokens]
    cycles = sum(r.cycles for r in runs)
    if not cycles:
        return None
    return 1e3 * sum(r.t1 - r.t0 for r in runs) / cycles
