"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    busy = ctx.busy_s()
    if busy is None or not ctx.run.window_s:
        return None
    return 100.0 * (1.0 - busy / ctx.run.window_s)
