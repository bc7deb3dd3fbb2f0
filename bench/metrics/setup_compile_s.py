"""Set-up spent compiling, or loading from the persistent cache: the sum
of the program's ``serve.engine.compile`` spans from process start to the
window's open."""
LAYER, UNIT, SOURCE, MOVES = "engine", "s", "host_clock", "setup_s"


def read(ctx):
    rec = getattr(ctx, "spans", None)
    if rec is None:
        return None
    return sum(min(s.t1, ctx.res.open) - s.t0 for s in rec.spans
               if s.name == "serve.engine.compile" and s.t0 < ctx.res.open)
