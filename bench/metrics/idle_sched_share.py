"""Share of the traced slice in which no operation ran on the device
while the innermost open program span was the scheduler's: admission,
the policy (LazyBatching and its slack predictor), the arbiter, or the
run boundary's epilogue (token streaming, finish hooks). Each idle gap
is split by its intersection with the spans."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "sla_attainment"

SPANS = ("serve.sched.admit", "serve.sched.policy", "serve.sched.arbiter",
         "serve.sched.epilogue")


def read(ctx):
    st = getattr(ctx, "span_trace", None)
    if st is None or not st.spans:
        return None
    return st.idle_share(SPANS)
