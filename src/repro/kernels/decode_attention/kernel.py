"""Flash-decode: single-token attention against a long KV cache, split over
the sequence (split-K) so the dominant loop streams the cache through VMEM
in lane-aligned 128-token tiles.

Grid = (B, K, nS).  The last axis iterates sequentially on TPU, carrying the
online-softmax state in VMEM scratch; device-level split-K parallelism comes
from sharding the cache's T dim over the "model" mesh axis (the partial
max/sum then combine with all-reduces inserted by SPMD — see
models/attention.py `decode_attend`).  Within a chip this kernel is the
per-shard inner loop.

cache_len arrives via scalar prefetch (SMEM) so masking is dynamic without
re-compilation per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc,
                   *, scale, block_t, n_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    b = pl.program_id(0)
    base = ti * block_t

    @pl.when(base < len_ref[b])
    def _compute():
        q = q_ref[0, 0]                                       # (G, Dh)
        k = k_ref[0, 0]                                       # (block_t, Dh)
        # masks straight from 2-D iotas (a bool-vector reshape is refused
        # by the TPU compiler)
        vrow = base + jax.lax.broadcasted_iota(
            jnp.int32, v_ref.shape[2:], 0) < len_ref[b]       # (block_t, Dh)
        scol = base + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_t), 1) < len_ref[b]  # (G, block_t)
        # zero invalid rows: when T % block_t != 0 the final block reads
        # out-of-bounds rows (NaN in interpret mode); their p weight is 0
        # but 0*NaN would still poison the p@v contraction.
        v = jnp.where(vrow, v_ref[0, 0], 0.0)
        s = jax.lax.dot_general(
            q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (G, block_t)
        s = jnp.where(scol, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (G, Dh)
        acc_sc[...] = acc_sc[...] * alpha[..., None] + pv
        m_sc[...] = m_new

    @pl.when(ti == n_t - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[..., None]).astype(o_ref.dtype)


def decode_attention_kernel(q, k_cache, v_cache, cache_len, *, block_t=128,
                            interpret=False):
    """q: (B,K,G,Dh); caches: (B,K,T,Dh); cache_len: (B,) int32."""
    B, K, G, Dh = q.shape
    T = k_cache.shape[2]
    block_t = min(block_t, T)
    n_t = pl.cdiv(T, block_t)
    scale = 1.0 / (Dh ** 0.5)

    kernel = functools.partial(_decode_kernel, scale=scale, block_t=block_t,
                               n_t=n_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, ti, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, Dh),
                         lambda b, h, ti, lens: (b, h, ti, 0)),
            pl.BlockSpec((1, 1, block_t, Dh),
                         lambda b, h, ti, lens: (b, h, ti, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, ti, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    # scalar-prefetch operand indexed per grid cell b
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lens, q, k_cache, v_cache)
