"""Engine host time per run: mean over the ``serve.engine.run`` spans in
the window of their duration less their ``serve.engine.sync`` child
(planning, dispatch and epilogue; the program's recorder)."""
LAYER, UNIT, SOURCE, MOVES = "engine", "ms", "host_clock", "sla_attainment"


def read(ctx):
    rec = getattr(ctx, "spans", None)
    if rec is None:
        return None
    lo, hi = ctx.res.open, ctx.res.close
    host = {}
    for i, s in enumerate(rec.spans):
        if s.name == "serve.engine.run" and lo <= s.t0 and s.t1 <= hi:
            host[i] = s.t1 - s.t0
    for s in rec.spans:
        if s.name == "serve.engine.sync" and s.parent in host:
            host[s.parent] -= s.t1 - s.t0
    if not host:
        return None
    return 1e3 * sum(host.values()) / len(host)
