"""Share of device operation time spent in collectives (all-reduce and the
like), from the trace, averaged over the chips (device, mesh
collectives). Nothing to read where the trace holds no collective."""


def read(ctx):
    if ctx.trace is None:
        return None
    v = ctx.trace.collective_share()
    return None if not v else 100.0 * v
