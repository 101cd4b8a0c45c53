"""Mean device time of one run of the jitted decode step, from the trace
(model step)."""


def read(ctx):
    return ctx.step_ms("decode")
