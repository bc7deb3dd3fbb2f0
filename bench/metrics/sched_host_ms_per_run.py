"""Scheduler host time per run: wall time of each ``session.step`` that
ran a run, less the ``execute_run`` time inside it (policy, slack
predictor, arbiter, session bookkeeping, token streaming), mean over the
window."""
LAYER, UNIT, SOURCE, MOVES = "scheduler", "ms", "host_clock", "sla_attainment"


def read(ctx):
    res = ctx.res
    steps = [s for s in res.timeline.steps
             if s.exec_s > 0 and res.open <= s.t0 and s.t1 <= res.close]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 - s.exec_s for s in steps) / len(steps)
