"""jit'd public wrapper for the grouped-matmul Pallas kernel.

`grouped_matmul` takes the ragged layout (rows sorted by expert +
group_sizes) and builds the per-tile expert map.  Group boundaries must be
block_m-aligned (the dense-padding contract); `pad_group_sizes` produces
aligned layouts.  The model's dropless MoE layer (``models/moe.py``)
builds its tile map itself and calls the kernel directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grouped_matmul.kernel import grouped_matmul_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pad_group_sizes(group_sizes, block_m: int):
    """Round every group size up to a multiple of block_m."""
    return ((group_sizes + block_m - 1) // block_m) * block_m


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def grouped_matmul(x, w, group_sizes, *, block_m=128, block_n=128,
                   interpret=None):
    """x: (T,D) rows sorted by expert, each group block_m-aligned and padded
    with zero rows; w: (E,D,F); group_sizes: (E,) aligned sizes summing to
    <= T.  Returns (T,F) f32 (zero rows stay zero)."""
    T, D = x.shape
    E = w.shape[0]
    if interpret is None:
        interpret = not _on_tpu()
    n_tiles = T // block_m
    ends = jnp.cumsum(group_sizes)
    tile_starts = jnp.arange(n_tiles) * block_m
    # expert owning each row tile; tiles past all groups clamp to E-1 and
    # multiply against zero-padded x rows -> zero output.
    tile_ids = jnp.minimum(
        jnp.searchsorted(ends, tile_starts, side="right"), E - 1)
    return grouped_matmul_kernel(x, w, tile_ids, block_m=block_m,
                                 block_n=block_n, interpret=interpret)

