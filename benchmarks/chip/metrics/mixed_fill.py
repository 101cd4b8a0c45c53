"""Prompt-chunk columns over the columns that the window's mixed steps pay
for (mixed steps x slots x chunk width), from the engine's counters
(scheduler)."""


def read(ctx):
    c = ctx.counters
    if not c["mixed_steps"]:
        return None
    return 100.0 * c["chunk_tokens"] / (
        c["mixed_steps"] * ctx.num_slots * ctx.chunk_width)
