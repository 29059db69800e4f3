"""Mean time from ``TaskRepo.submit`` of a serve task to its first token,
over every bind in the window: match, image pull, engine build (weights
from the seed, KV pool), install and admission prefill."""

from benchkit.readers import mean


def read(ctx):
    v = mean(b["bind_ttft_s"] for b in ctx.run.binds)
    return None if v is None else 1e3 * v
