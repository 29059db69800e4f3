"""jit'd public wrapper for the paged flash-decode Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.runtime.mesh import MODEL_AXIS


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           interpret=None):
    """q: (B,H,Dh) one new token per sequence; pools: (nb, bs, K, Dh) shared
    block pool; block_tables: (B, mb) int32; cache_len: scalar or (B,) valid
    count.  Returns (B,H,Dh).

    The logical sequence of row ``b`` is ``pool[table[b, p // bs], p % bs]``
    for ``p < cache_len[b]``; table entries past the row's allocation point
    at the reserved scratch block (id 0) and are masked out by the ragged
    lengths, so they are never read into the softmax."""
    B, H, Dh = q.shape
    K = k_pool.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    if interpret is None:
        interpret = not _on_tpu()
    # one query per row, at position cache_len - 1
    q_off = jnp.asarray(cache_len, jnp.int32) - 1
    o = paged_attention_kernel(q.reshape(B, K, G, Dh), k_pool, v_pool,
                               block_tables, q_off, group=G,
                               interpret=interpret)
    return o.reshape(B, H, Dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(q, k_pool, v_pool, block_tables, q_off, *,
                           interpret=None):
    """k-query flash-decode for speculative verify.  q: (B,S,H,Dh) — the
    S = k+1 verify queries of each row, query ``s`` at absolute position
    ``q_off[b] + s``; pools: (nb, bs, K, Dh); block_tables: (B, mb) int32;
    q_off: scalar or (B,) base positions.  Returns (B,S,H,Dh).

    One walk of the row's block table serves all S queries (a staircase
    causal mask instead of S ragged lengths), so the verify step streams
    each KV block from HBM once, not S times."""
    B, S, H, Dh = q.shape
    K = k_pool.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    if interpret is None:
        interpret = not _on_tpu()
    # kernel rows r = s*G + g, grouped under their kv head
    qg = q.reshape(B, S, K, G, Dh).transpose(0, 2, 1, 3, 4)
    o = paged_attention_kernel(qg.reshape(B, K, S * G, Dh), k_pool, v_pool,
                               block_tables, q_off, group=G,
                               interpret=interpret)
    o = o.reshape(B, K, S, G, Dh).transpose(0, 2, 1, 3, 4)
    return o.reshape(B, S, H, Dh)


# --------------------------------------------------------------------------
# tensor-parallel (head-sharded) wrappers
# --------------------------------------------------------------------------
# Each mesh shard runs the SAME Pallas kernel on its local contiguous head
# slice: q on dim 1 (decode) / dim 2 (verify) over "model", pools on their
# K dim (2), block tables + lengths replicated (they are the scalar-prefetch
# operands — every shard walks the same table).  The contiguous-heads split
# aligns with the kv-group mapping (query head h attends kv head h // G), so
# shard s owns query heads [s*H/m, (s+1)*H/m) and exactly the kv heads
# [s*K/m, (s+1)*K/m) they attend — no cross-shard communication, and every
# per-head softmax is bitwise identical to the single-device kernel.
# check_vma=False: pallas_call inside shard_map cannot prove replication.
# Eligibility is :func:`repro.runtime.mesh.tp_heads`.

def _len_spec(x) -> P:
    return P() if jnp.ndim(x) == 0 else P(*([None] * jnp.ndim(x)))


def paged_decode_attention_tp(q, k_pool, v_pool, block_tables, cache_len,
                              mesh, *, interpret=None):
    """Head-sharded paged_decode_attention under shard_map.  Same contract;
    q (B,H,Dh) sharded on H, pools (nb,bs,K,Dh) sharded on K, output
    (B,H,Dh) sharded on H.  Requires
    :func:`repro.runtime.mesh.tp_heads`."""
    if interpret is None:
        interpret = not _on_tpu()
    fn = jax.shard_map(
        functools.partial(paged_decode_attention, interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, MODEL_AXIS, None), P(None, None, MODEL_AXIS, None),
                  P(None, None, MODEL_AXIS, None), P(None, None),
                  _len_spec(cache_len)),
        out_specs=P(None, MODEL_AXIS, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, block_tables, cache_len)


def paged_verify_attention_tp(q, k_pool, v_pool, block_tables, q_off,
                              mesh, *, interpret=None):
    """Head-sharded paged_verify_attention under shard_map.  q (B,S,H,Dh)
    sharded on H; pools on K; output sharded on H."""
    if interpret is None:
        interpret = not _on_tpu()
    fn = jax.shard_map(
        functools.partial(paged_verify_attention, interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, None, MODEL_AXIS, None),
                  P(None, None, MODEL_AXIS, None),
                  P(None, None, MODEL_AXIS, None), P(None, None),
                  _len_spec(q_off)),
        out_specs=P(None, None, MODEL_AXIS, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, block_tables, q_off)
