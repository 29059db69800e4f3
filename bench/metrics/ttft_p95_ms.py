"""95th percentile of time to first token from each request's DUE time,
over every request due in the window.  A request that never answered
counts as waiting until the run gave up on it."""

from benchkit.loads import DRAIN_S
from benchkit.readers import percentile


def read(ctx):
    run = ctx.run
    give_up = run.window_s + DRAIN_S
    v = percentile([give_up if r["failed"] else r["first_token_s"]
                    for r in run.requests], 95)
    return None if v is None else 1e3 * v
