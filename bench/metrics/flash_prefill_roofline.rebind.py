"""Flash prefill kernel against its roofline: the causal attention work of
every admission prefill in the window (prompt as sent, every layer) over
the kernel's device time in the trace."""

from benchkit import trace
from benchkit.readers import roofline_share


def read(ctx):
    if ctx.rows is None:
        return None
    cost = ctx.cost("flash_prefill")
    secs = trace.kernel_s(ctx.rows, cost.TRACE_NAMES)
    if secs <= 0:
        return None
    layers = int(ctx.model["num_hidden_layers"])
    flops = nbytes = 0.0
    for r in ctx.run.requests:
        f, b = cost.cost(ctx.model, len(r["prompt"]))
        flops += layers * f
        nbytes += layers * b
    return roofline_share(flops, nbytes, secs, ctx.peaks)
