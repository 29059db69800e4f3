"""Set-up: cluster, pilot, image pull (compiles), weights, warm-up."""


def read(ctx):
    return ctx.run.setup_s
