"""Late-bind control path of each bind: from the start of its
``repro.repo.submit`` span to the start of the payload's
``repro.payload.weights`` span (match, image pull, staging, container
start, startup spec and the thread hand-offs between them); mean over
the window's binds."""

from benchkit.spans import SUBMIT, WEIGHTS, mean_ms, paired


def read(ctx):
    return mean_ms(paired(ctx.rows, SUBMIT, WEIGHTS, to_end=False))
