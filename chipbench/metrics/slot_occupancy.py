"""Share of slot rows doing useful work per decode wave over the window,
from the engine's counters: delta n_slot_steps / (delta n_decode_steps * n_slots)."""


def read(ctx):
    o, c = ctx.window.counters_open, ctx.window.counters_close
    steps = c["n_decode_steps"] - o["n_decode_steps"]
    if steps <= 0:
        return None
    return 100.0 * (c["n_slot_steps"] - o["n_slot_steps"]) / (steps * ctx.cell["n_slots"])
