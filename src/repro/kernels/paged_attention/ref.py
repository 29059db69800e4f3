"""Pure-jnp oracle for paged single-token decode attention.

The KV cache is a shared block pool ``(num_blocks, block_size, W)``, a
position's K heads merged in the minor dim (head ``h`` in lanes
``[h*Dh, (h+1)*Dh)``, padding lanes past ``K*Dh``); each batch row owns
a *block table* ``(max_blocks,)`` of physical block ids mapping logical
position ``p`` to ``pool[table[p // bs], p % bs]``.
The oracle gathers each row's logical view and defers to the dense
decode-attention oracle, so kernel-vs-ref equality also certifies the
gather semantics.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention.ref import decode_attention_ref


def gather_kv(pool, block_tables, heads=None):
    """pool: (nb, bs, ...); block_tables: (B, mb) int32.
    Returns the per-row logical view (B, mb*bs, ...).  ``heads`` = (K, Dh)
    reads a GQA pool's merged row back as its K heads: (B, mb*bs, K, Dh)."""
    B, mb = block_tables.shape
    g = pool[block_tables]                     # (B, mb, bs, ...)
    g = g.reshape((B, mb * pool.shape[1]) + pool.shape[2:])
    if heads is None:
        return g
    K, Dh = heads
    return g[..., :K * Dh].reshape(g.shape[:2] + (K, Dh))


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, cache_len,
                               *, kv_heads):
    """q: (B,H,Dh); pools: (nb, bs, W); block_tables: (B, mb) int32;
    cache_len: scalar or (B,) valid-entry count.  Returns (B,H,Dh)."""
    heads = (kv_heads, q.shape[-1])
    kg = gather_kv(k_pool, block_tables, heads)
    vg = gather_kv(v_pool, block_tables, heads)
    return decode_attention_ref(q, kg, vg, cache_len)


def paged_verify_attention_ref(q, k_pool, v_pool, block_tables, q_off, *,
                               kv_heads):
    """k-query speculative-verify oracle.  q: (B,S,H,Dh) — query ``s`` of
    row ``b`` sits at absolute position ``q_off[b] + s`` and attends the
    causal prefix ``t <= q_off[b] + s``; the per-query loop defers to the
    single-token oracle so each query's math (shapes, masks, reduction
    order) is EXACTLY one plain decode step's.  Returns (B,S,H,Dh)."""
    heads = (kv_heads, q.shape[-1])
    kg = gather_kv(k_pool, block_tables, heads)
    vg = gather_kv(v_pool, block_tables, heads)
    T = kg.shape[1]
    outs = [decode_attention_ref(q[:, s], kg, vg,
                                 jnp.minimum(q_off + s + 1, T))
            for s in range(q.shape[1])]
    return jnp.stack(outs, axis=1)
