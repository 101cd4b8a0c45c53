"""Mean device time of one run of the jitted mixed step, from the trace,
over the four chips of the tensor-parallel mesh (model step)."""


def read(ctx):
    return ctx.step_ms("mixed")
