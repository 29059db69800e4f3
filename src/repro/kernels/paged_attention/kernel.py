"""Paged flash-decode: few-query attention over a block-pool KV cache.

The pool ``(num_blocks, block_size, K, Dh)`` is shared by every sequence;
a per-row block table maps logical position ``p`` of batch row ``b`` to
``pool[table[b, p // bs], p % bs]``.  Grid = (B, mb): the last axis walks
the row's block table sequentially, carrying the online-softmax state of
every KV head in VMEM scratch.  Each grid cell streams one whole physical
block, all K heads at once, so the block's last two dims ``(K, Dh)`` are
the pool's own — the TPU tiling rule holds for any head count.  Both the
query offsets AND the block tables arrive via scalar prefetch (SMEM), so
the physical block to stream into VMEM is chosen by the BlockSpec
index_map — the gather never materializes a contiguous copy of the
sequence, which is the whole point of paging: HBM holds exactly the live
blocks, and admission-time block remapping (prefix reuse) costs zero
copies.

One kernel serves decode (one query per row) and speculative verify
(``n_q`` queries per row): query ``s`` of row ``b`` sits at absolute
position ``off[b] + s`` and attends ``t <= off[b] + s``.  Blocks wholly
past the deepest query skip their compute (their table entries point at
the reserved scratch block), so short sequences pay for the blocks they
own, not for the table width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(off_ref, btab_ref, q_ref, k_ref, v_ref, o_ref,
                  m_sc, l_sc, acc_sc, *, scale, block_size, n_b, n_kv,
                  group):
    """q rows are ``r = s * group + g``: query ``s`` of the row, head ``g``
    of the kv group.  Masks come straight from 2-D iotas."""
    b = pl.program_id(0)
    ti = pl.program_id(1)
    rows = q_ref.shape[2]
    n_q = rows // group

    @pl.when(ti == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    base = ti * block_size
    # the deepest query reaches t <= off + n_q - 1
    reach = off_ref[b] + n_q - 1

    @pl.when(base <= reach)
    def _compute():
        # staircase causal mask: row r = s*G + g covers t <= off + s
        s_idx = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size),
                                         0) // group
        tcol = base + jax.lax.broadcasted_iota(jnp.int32, (rows, block_size),
                                               1)
        valid = tcol <= off_ref[b] + s_idx
        # zero rows past the reach so 0-weight garbage can't poison p@v
        vrow = base + jax.lax.broadcasted_iota(
            jnp.int32, (block_size, v_ref.shape[3]), 0) <= reach
        for h in range(n_kv):
            q = q_ref[0, h]                               # (rows, Dh)
            k = k_ref[0, :, h, :]                         # (block_size, Dh)
            v = jnp.where(vrow, v_ref[0, :, h, :], 0.0)
            s = jax.lax.dot_general(
                q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            # a row can be ENTIRELY masked in this block (shallow query,
            # deep block): then m_new == NEG_INF and exp(s - m_new) == 1,
            # not 0 — zero masked entries so they never enter l / acc
            p = jnp.where(valid, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * alpha + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                p.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # (rows, Dh)
            acc_sc[h] = acc_sc[h] * alpha[..., None] + pv
            m_sc[h] = m_new

    @pl.when(ti == n_b - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[..., None]).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pool, v_pool, block_tables, q_off, *,
                           group, interpret=False):
    """q: (B, K, n_q*G, Dh), row ``s*G + g`` is query ``s`` (absolute
    position ``q_off[b] + s``) of kv-group head ``g``; pools:
    (nb, block_size, K, Dh); block_tables: (B, mb) int32 physical block
    ids; q_off: (B,) int32 position of each row's first query."""
    B, K, R, Dh = q.shape
    block_size = k_pool.shape[1]
    mb = block_tables.shape[1]
    scale = 1.0 / (Dh ** 0.5)

    kernel = functools.partial(_paged_kernel, scale=scale,
                               block_size=block_size, n_b=mb, n_kv=K,
                               group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # q_off, block_tables
        grid=(B, mb),
        in_specs=[
            pl.BlockSpec((1, K, R, Dh), lambda b, ti, off, btab: (b, 0, 0, 0)),
            # the paged gather: the physical block streamed into VMEM is
            # picked from the prefetched table, per grid cell
            pl.BlockSpec((1, block_size, K, Dh),
                         lambda b, ti, off, btab: (btab[b, ti], 0, 0, 0)),
            pl.BlockSpec((1, block_size, K, Dh),
                         lambda b, ti, off, btab: (btab[b, ti], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, R, Dh),
                               lambda b, ti, off, btab: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, R), jnp.float32),
            pltpu.VMEM((K, R), jnp.float32),
            pltpu.VMEM((K, R, Dh), jnp.float32),
        ],
    )
    off = jnp.broadcast_to(jnp.asarray(q_off, jnp.int32), (B,))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(off, block_tables.astype(jnp.int32), q, k_pool, v_pool)
