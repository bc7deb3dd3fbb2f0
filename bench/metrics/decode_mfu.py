"""Model FLOPs of the decode cycles run in the window (every matmul
weight incl. the head once per row, attention over each row's context;
``bench.work``) over decode ``execute_run`` wall time times the chip's
bf16 peak."""
LAYER, UNIT, SOURCE, MOVES = "model step", "%", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import runs_between
    from bench.work import decode_step_flops
    runs = [r for r in runs_between(ctx.res.timeline, ctx.res.open,
                                    ctx.res.close)
            if r.cycles and not r.prefill_tokens]
    wall = sum(r.t1 - r.t0 for r in runs)
    if wall <= 0:
        return None
    flops = sum(decode_step_flops(ctx.dims, [x + c for x in r.ctxs])
                for r in runs for c in range(r.cycles))
    return 100.0 * flops / (wall * ctx.peak["bf16_flops_per_s"])
