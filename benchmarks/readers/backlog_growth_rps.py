"""How fast the front door's queue grew over the window (requests/s):
the offered load above what the engine takes."""


def read(ctx):
    if ctx.backlog is None:
        return None
    return (ctx.backlog[1] - ctx.backlog[0]) / (ctx.t1 - ctx.t0)
