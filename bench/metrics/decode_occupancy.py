"""Share of decode lanes that carried a request: slot_steps_busy over
decode_dispatches x max_batch, from the engine's counters over the window."""


def read(ctx):
    c = ctx.counters
    if not c.get("decode_dispatches"):
        return None
    return 100.0 * c["slot_steps_busy"] / (c["decode_dispatches"] * c["max_batch"])
