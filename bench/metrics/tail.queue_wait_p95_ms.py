"""Tail of queue wait: the 95th percentile, over requests due in the
window, of the start of the first ``serve.engine.run`` holding the
request less its ``serve.submit`` stamp (the program's recorder; one that
never ran is censored at the end of the run)."""
LAYER, UNIT, SOURCE, MOVES = "scheduler", "ms", "host_clock", "sla_attainment"


def read(ctx):
    from bench.harness import percentile, window_requests
    rec = getattr(ctx, "spans", None)
    if rec is None:
        return None
    submit, first = {}, {}
    for s in rec.spans:
        if s.name == "serve.submit":
            submit.setdefault(s.attrs["rid"], s.t0)
        elif s.name == "serve.engine.run" and "rids" in s.attrs:
            for rid in s.attrs["rids"]:
                first.setdefault(rid, s.t0)
    waits = [first.get(rid, ctx.res.end) - submit[rid]
             for rid in (r.request.rid for r in window_requests(ctx.res)
                         if r.request is not None) if rid in submit]
    v = percentile(waits, 95)
    return None if v is None else 1e3 * v
