"""1 - (union of device-busy intervals) / traced window, averaged over the
chips (device)."""


def read(ctx):
    return ctx.idle_share()
