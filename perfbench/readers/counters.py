"""Readers of the program's own counters (`program_counter`), as the runner
copied them into `ctx.counters` around the window."""


def counter(ctx, name, scale=1.0):
    value = ctx.counters.get(name)
    return None if value is None else value * scale
