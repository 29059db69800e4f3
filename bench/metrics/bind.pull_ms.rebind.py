"""Image pull inside each bind (pilot history ``bind_seconds``: the
registry pull the pod patch waits on), mean over the window's binds."""

from benchkit.readers import mean


def read(ctx):
    v = mean(b["pull_s"] for b in ctx.run.binds)
    return None if v is None else 1e3 * v
