"""Grouped-matmul kernel against its roofline: the routed-expert work of
every admission prefill in the window (prompt as sent, top-k experts, the
SwiGLU up, gate and down, every layer) over the kernel's device time in
the trace.

A device op is the kernel's when its own name, not an operand it reads,
is one of the cost's ``TRACE_NAMES`` (the trace names each op by its HLO
text, ``%grouped_matmul.36 = f32[...] custom-call(...)``, and the ops
that consume the kernel's output name it among their operands)."""

from benchkit import trace
from benchkit.readers import roofline_share


def _own_name(op: str) -> str:
    return op.lstrip("%").split(" ", 1)[0].split(".", 1)[0]


def kernel_s(rows, names) -> float:
    """Device seconds of the ops named ``names``, averaged over planes."""
    planes = trace.device_ops(rows)
    if not planes:
        return 0.0
    tot = sum(d for evs in planes.values() for n, _, d in evs
              if _own_name(n) in names)
    return tot / len(planes) / 1e9


def read(ctx):
    if ctx.rows is None:
        return None
    cost = ctx.cost("moe_gmm")
    secs = kernel_s(ctx.rows, cost.TRACE_NAMES)
    if secs <= 0:
        return None
    layers = int(ctx.model["num_hidden_layers"])
    flops = nbytes = 0.0
    for r in ctx.run.requests:
        f, b = cost.cost(ctx.model, len(r["prompt"]))
        flops += layers * f
        nbytes += layers * b
    return roofline_share(flops, nbytes, secs, ctx.peaks)
