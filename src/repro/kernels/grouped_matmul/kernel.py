"""MoE grouped expert matmul (dense-padded group tiling) for TPU.

Tokens arrive sorted by expert with every group padded to a multiple of
block_m (the "dense padding" that trades a few zero rows for fully regular
MXU tiles — the TPU-native answer to GPU megablocks' ragged CSR tiling).
A per-row-tile expert id array rides in via scalar prefetch, and the weight
BlockSpec index_map selects the expert's (D, block_n) slab — so one kernel
instance streams x tiles while hopping expert weights without any gather.

Grid = (nM, nN); x tile (block_m, D) and w slab (D, block_n) both live in
VMEM; D (<= 4096 for all assigned MoE archs) rides whole, so each tile is a
single MXU matmul with no k-loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# the kernel's name in HLO and in the device trace
TRACE_NAME = "grouped_matmul"


def _gmm_kernel(gids_ref, x_ref, w_ref, y_ref):
    x = x_ref[...]                                            # (block_m, D)
    w = w_ref[0]                                              # (D, block_n)
    y_ref[...] = jax.lax.dot_general(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)


def grouped_matmul_kernel(x, w, tile_expert_ids, *, block_m=128, block_n=128,
                          interpret=False):
    """x: (T,D) with T % block_m == 0, rows sorted + padded by expert;
    w: (E,D,F); tile_expert_ids: (T/block_m,) int32.  Returns (T,F) f32."""
    T, D = x.shape
    E, _, F = w.shape
    assert T % block_m == 0, (T, block_m)
    block_n = min(block_n, F)
    assert F % block_n == 0, (F, block_n)
    grid = (T // block_m, F // block_n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, ni, gids: (mi, 0)),
            pl.BlockSpec((1, D, block_n),
                         lambda mi, ni, gids: (gids[mi], 0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, gids: (mi, ni)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), jnp.float32),
        interpret=interpret,
        name=TRACE_NAME,
    )(tile_expert_ids.astype(jnp.int32), x, w)
