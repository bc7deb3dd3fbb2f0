"""Set-up: process start to the first due request (engine build, weights
made on the device, compiles or persistent-cache loads, warm-up)."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "s", "host_clock", None


def read(ctx):
    return ctx.setup_s
