"""Routed-expert grouped matmul: the work the routing requires.

For one layer over ``tokens`` tokens (the prompt as sent, not the bucket
it is padded to): each token's ``num_experts_per_tok`` experts run the
SwiGLU up, gate and down projections, 2 FLOPs per multiply-add.  Bytes,
in bf16: every expert's three weight matrices read once, and each routed
row read into and written out of each projection once (d-wide into up
and gate and out of down, f-wide out of up and gate and into down).  Rows
that pad an expert's group to whole tiles are not work, so any
implementation is read against the same count.
"""

# the op names of the Pallas kernel (kernels/grouped_matmul) in the trace
TRACE_NAMES = ("grouped_matmul",)


def cost(model: dict, tokens: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's routed experts over ``tokens``."""
    d = int(model["hidden_size"])
    f = int(model["intermediate_size"])
    experts = int(model["num_local_experts"])
    pairs = tokens * int(model["num_experts_per_tok"])
    flops = 2 * 3 * pairs * d * f
    nbytes = 2 * (3 * experts * d * f + 3 * pairs * (d + f))
    return flops, nbytes
