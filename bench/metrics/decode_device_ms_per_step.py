"""Device time of the decode megastep per decode cycle: the
``jit_decode_megastep`` programs on the trace's ``XLA Modules`` line
inside the traced slice, over the ``decode_cycles`` the program counted
in the same slice."""
LAYER, UNIT, SOURCE, MOVES = "model step", "ms", "device_trace", \
    "sla_attainment"

PROGRAM = "jit_decode_megastep"


def read(ctx):
    rec, st = getattr(ctx, "spans", None), getattr(ctx, "span_trace", None)
    if rec is None or st is None or ctx.res.trace_bounds is None:
        return None
    a, b = ctx.res.trace_bounds
    cycles = sum(k for name, t, k in rec.counters
                 if name == "decode_cycles" and a <= t <= b)
    t = st.module_seconds(PROGRAM)
    if not cycles or t <= 0:
        return None
    return 1e3 * t / cycles
