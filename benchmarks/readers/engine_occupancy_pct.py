"""Busy decode slots per engine tick, as a share of the slots."""


def read(ctx):
    ticks = [t for t in ctx.ticks if ctx.t0 <= t[0] < ctx.t1]
    if not ticks:
        return None
    slots = ctx.config["serve"]["slots"]
    return 100.0 * sum(t[2] for t in ticks) / (len(ticks) * slots)
