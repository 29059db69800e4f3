"""Paged decode attention: the work one query needs over its LIVE cache.

One new token per sequence attends over ``ctx`` cached positions (itself
included).  The kernel's grid covers every block of ``max_len`` whatever
the live length is; the work counted here is the live context only, so a
kernel that skips dead blocks is read against the same work.
"""

TRACE_NAMES = ("_paged_kernel", "paged_decode_attention", "paged_attention")


def cost(model: dict, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode attention for one sequence
    with ``ctx`` live positions: bf16 K and V read once, q and o once."""
    h = int(model["num_attention_heads"])
    kv = int(model["num_key_value_heads"])
    dh = int(model.get("head_dim") or int(model["hidden_size"]) // h)
    flops = 2 * 2 * h * dh * ctx
    nbytes = 2 * (2 * ctx * kv * dh + 2 * h * dh)
    return flops, nbytes
