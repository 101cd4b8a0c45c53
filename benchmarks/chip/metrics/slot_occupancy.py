"""Mean active slots over the engine's slots, per step of the window, from
the engine's own counters (scheduler)."""


def read(ctx):
    c = ctx.counters
    if not c["steps"]:
        return None
    return 100.0 * c["active_slot_steps"] / (c["steps"] * ctx.num_slots)
