"""Causal flash-attention prefill: the work the algorithm needs.

For one layer over a prompt of ``s`` tokens (the prompt as sent, not the
bucket it is padded to): QK^T and PV over the causal triangle, 2 FLOPs
per multiply-add, and each of q, k, v, o read or written once in bf16.
"""

TRACE_NAMES = ("_attn_kernel", "flash_attention")


def cost(model: dict, s: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's causal attention over ``s`` tokens."""
    h = int(model["num_attention_heads"])
    kv = int(model["num_key_value_heads"])
    dh = int(model.get("head_dim") or int(model["hidden_size"]) // h)
    pairs = s * (s + 1) / 2                     # causal (query, key) pairs
    flops = 2 * 2 * h * dh * pairs              # QK^T and PV
    nbytes = 2 * (2 * s * h * dh + 2 * s * kv * dh)   # q, o and k, v
    return flops, nbytes
