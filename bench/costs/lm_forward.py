"""Required FLOPs of a decoder-only LM forward, from the config's sizes.

Per token: 2 FLOPs per weight it multiplies (q, k, v, o projections; the
gated FFN or, for sparse experts, the router and ``num_experts_per_tok``
experts; the LM head) plus causal attention over the positions before
it.  Embedding lookups, norms and softmax are left out.
"""


def weight_flops_per_token(model: dict) -> float:
    d = int(model["hidden_size"])
    h = int(model["num_attention_heads"])
    kv = int(model["num_key_value_heads"])
    dh = int(model.get("head_dim") or d // h)
    f = int(model["intermediate_size"])
    proj = d * dh * (2 * h + 2 * kv)
    experts = int(model.get("num_local_experts", 0))
    if experts:
        ffn = d * experts + int(model["num_experts_per_tok"]) * 3 * d * f
    else:
        ffn = 3 * d * f
    layers = int(model["num_hidden_layers"])
    return 2.0 * (layers * (proj + ffn) + d * int(model["vocab_size"]))


def attention_flops(model: dict, ctx: int) -> float:
    """Causal attention FLOPs of one token at context ``ctx`` (all
    layers): QK^T and PV over ``ctx`` positions."""
    d = int(model["hidden_size"])
    h = int(model["num_attention_heads"])
    dh = int(model.get("head_dim") or d // h)
    return 2.0 * 2 * h * dh * ctx * int(model["num_hidden_layers"])


def sequence_flops(model: dict, n: int) -> float:
    """FLOPs of a causal forward over ``n`` tokens (prefill or decode:
    token ``i`` attends over ``i + 1`` positions)."""
    return (n * weight_flops_per_token(model)
            + attention_flops(model, n * (n + 1) // 2))
