"""Share of the traced slice in which no operation ran on the device
while the innermost open program span was the engine's host work:
planning a run, dispatching or compiling a program, or the run's
epilogue. Each idle gap is split by its intersection with the spans."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "sla_attainment"

SPANS = ("serve.engine.plan", "serve.engine.dispatch",
         "serve.engine.compile", "serve.engine.epilogue")


def read(ctx):
    st = getattr(ctx, "span_trace", None)
    if st is None or not st.spans:
        return None
    return st.idle_share(SPANS)
