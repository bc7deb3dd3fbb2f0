"""Share of its roofline that the Pallas ragged decode-attention kernel
reaches in the traced slice: the least time the chip needs for the work
the algorithm requires (q, K and V of each live row's valid context, and
the output, per layer and decode cycle; ``bench.work``) over the kernel's
device time in the trace."""
LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "sla_attainment"

# operation names of the kernel in a TPU trace
KERNEL = r"ragged_decode"


def read(ctx):
    from bench.harness import runs_between
    from bench.work import decode_attn_work, roofline_seconds
    if ctx.reduced is None:
        return None
    t = ctx.reduced.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    a, b = ctx.res.trace_bounds
    flops = bytes_ = 0
    for r in runs_between(ctx.res.timeline, a, b):
        for c in range(r.cycles):
            f, by = decode_attn_work(ctx.dims, [x + c for x in r.ctxs])
            flops += f * ctx.dims["num_hidden_layers"]
            bytes_ += by * ctx.dims["num_hidden_layers"]
    return 100.0 * roofline_seconds(flops, bytes_, ctx.peak) / t
