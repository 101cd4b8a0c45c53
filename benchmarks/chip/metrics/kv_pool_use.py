"""Mean pages holding tokens over the pages of the pool, per step of the
window, from the engine's own counters (KV pool)."""


def read(ctx):
    c = ctx.counters
    if not c["steps"] or not ctx.total_pages:
        return None
    return 100.0 * c["pages_used_steps"] / (c["steps"] * ctx.total_pages)
