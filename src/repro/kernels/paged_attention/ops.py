"""jit'd public wrapper for the paged flash-decode Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention.kernel import (paged_attention_kernel,
                                                  row_width)
from repro.runtime.mesh import MODEL_AXIS, mesh_axis_size, tp_heads


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("kv_heads", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           kv_heads, interpret=None):
    """q: (B,H,Dh) one new token per sequence; pools: (nb, bs, W) shared
    block pool, kv head ``h`` in lanes ``[h*Dh, (h+1)*Dh)`` (`row_width`
    gives ``W``); block_tables: (B, mb) int32; cache_len: scalar or (B,)
    valid count.  Returns (B,H,Dh).

    The logical sequence of row ``b`` is ``pool[table[b, p // bs], p % bs]``
    for ``p < cache_len[b]``; table entries past the row's allocation point
    at the reserved scratch block (id 0) and are masked out by the ragged
    lengths, so they are never read into the softmax."""
    B, H, Dh = q.shape
    K = kv_heads
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


@functools.partial(jax.jit, static_argnames=("kv_heads", "interpret"))
def paged_verify_attention(q, k_pool, v_pool, block_tables, q_off, *,
                           kv_heads, interpret=None):
    """k-query flash-decode for speculative verify.  q: (B,S,H,Dh) — the
    S = k+1 verify queries of each row, query ``s`` at absolute position
    ``q_off[b] + s``; pools: (nb, bs, W) as `paged_decode_attention`'s;
    block_tables: (B, mb) int32; q_off: scalar or (B,) base positions.
    Returns (B,S,H,Dh).

    One walk of the row's block table serves all S queries (a staircase
    causal mask instead of S ragged lengths), so the verify step streams
    each KV block from HBM once, not S times."""
    B, S, H, Dh = q.shape
    K = kv_heads
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
# merged-heads dim (2), block tables + lengths replicated (they are the
# scalar-prefetch operands — every shard walks the same table).  The
# contiguous-heads split aligns with the kv-group mapping (query head h
# attends kv head h // G), so shard s owns query heads [s*H/m, (s+1)*H/m)
# and exactly the kv heads [s*K/m, (s+1)*K/m) they attend, which are its
# lanes of the pool when the pool has no padding lanes (W == K*Dh) — no
# cross-shard communication, and every per-head softmax is bitwise
# identical to the single-device kernel.
# check_vma=False: pallas_call inside shard_map cannot prove replication.
# Eligibility is :func:`heads_shard`.

def heads_shard(mesh, kv_heads: int, num_heads: int, head_dim: int) -> bool:
    """True iff the paged kernels and pools can be head-sharded on
    ``mesh``: whole kv-groups per shard (:func:`repro.runtime.mesh.tp_heads`)
    and a pool row with no padding lanes, so an even split of the row is
    an even split of the heads."""
    return (tp_heads(mesh, kv_heads, num_heads)
            and row_width(kv_heads, head_dim) == kv_heads * head_dim)


def _len_spec(x) -> P:
    return P() if jnp.ndim(x) == 0 else P(*([None] * jnp.ndim(x)))


def _shard_heads(mesh, kv_heads: int) -> int:
    return kv_heads // mesh_axis_size(mesh, MODEL_AXIS)


def paged_decode_attention_tp(q, k_pool, v_pool, block_tables, cache_len,
                              mesh, *, kv_heads, interpret=None):
    """Head-sharded paged_decode_attention under shard_map.  Same contract;
    q (B,H,Dh) sharded on H, pools (nb,bs,K*Dh) sharded on their heads,
    output (B,H,Dh) sharded on H.  Requires :func:`heads_shard`."""
    if interpret is None:
        interpret = not _on_tpu()
    fn = jax.shard_map(
        functools.partial(paged_decode_attention,
                          kv_heads=_shard_heads(mesh, kv_heads),
                          interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, MODEL_AXIS, None), P(None, None, MODEL_AXIS),
                  P(None, None, MODEL_AXIS), P(None, None),
                  _len_spec(cache_len)),
        out_specs=P(None, MODEL_AXIS, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, block_tables, cache_len)


def paged_verify_attention_tp(q, k_pool, v_pool, block_tables, q_off,
                              mesh, *, kv_heads, interpret=None):
    """Head-sharded paged_verify_attention under shard_map.  q (B,S,H,Dh)
    sharded on H; pools on their heads; output sharded on H."""
    if interpret is None:
        interpret = not _on_tpu()
    fn = jax.shard_map(
        functools.partial(paged_verify_attention,
                          kv_heads=_shard_heads(mesh, kv_heads),
                          interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, None, MODEL_AXIS, None),
                  P(None, None, MODEL_AXIS),
                  P(None, None, MODEL_AXIS), P(None, None),
                  _len_spec(q_off)),
        out_specs=P(None, None, MODEL_AXIS, None),
        check_vma=False)
    return fn(q, k_pool, v_pool, block_tables, q_off)
