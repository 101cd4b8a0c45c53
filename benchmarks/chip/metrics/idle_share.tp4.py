"""1 - (union of device-busy intervals) / traced window, averaged over the
four chips of the tensor-parallel mesh (device)."""


def read(ctx):
    return ctx.idle_share()
