"""Mixture-of-experts FFN: top-k router, dropless expert dispatch.

Both paths are dropless: every token reaches all ``top_k`` of its experts
whatever the load, as the published models route.

* ``apply_moe`` (training and the one-shot admission prefill): the
  token-expert pairs are sorted by expert and each expert's group is
  padded to whole row tiles (the megablox layout: at most
  ``T·k + E·(bm-1)`` rows for ``T`` tokens, no capacity).  One grouped
  matmul per projection runs over the sorted rows, each row tile against
  its expert's weights, and the combine reads each pair's row back and
  weights it by the normalised top-k router weight.  On a TPU the grouped
  matmul is the Pallas kernel (``kernels/grouped_matmul``); on the CPU,
  and inside a multi-device layout (XLA cannot partition a Mosaic call),
  it is a jnp product over the same tiles.
* ``apply_moe_dense`` (decode, chunked prefill, verify): every expert on
  the new token(s), weighted by the (zeroed non-top-k) router gates.
  Decode is memory-bound — all expert weights stream from HBM once either
  way — and the path has no gather or scatter.

Sharding: the serve rules shard ``up``/``gate`` on the expert hidden (F)
dim and keep ``down`` replicated (``runtime/sharding.py``); the hidden
activations are gathered before the down projection, so its contraction
keeps the single-device reduction order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.layers import act_fn, dense_init
from repro.runtime.sharding import constrain, constrain_replicated

ROW_TILE = 128          # rows per grouped-matmul tile at full expert load


def init_moe(key, cfg):
    m = cfg.moe
    D, E, F = cfg.d_model, m.num_experts, m.d_ff_expert
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (D, E)),
        "up": dense_init(ks[1], (E, D, F), in_axis=1),
        "down": dense_init(ks[2], (E, F, D), in_axis=1),
    }
    if cfg.mlp_gated:
        p["gate"] = dense_init(ks[3], (E, D, F), in_axis=1)
    return p


def router_probs(x, router_w):
    """f32 router logits -> probs.  x: (..., D)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def _top_k(probs, k: int):
    """Top-k weights renormalised to sum to 1, and their expert ids."""
    wts, idx = jax.lax.top_k(probs, k)
    wts = wts / jnp.maximum(jnp.sum(wts, axis=-1, keepdims=True), 1e-9)
    return wts, idx


def dropless_rows(n_tokens: int, cfg) -> tuple[int, int]:
    """``(rows, tile)`` of the sorted buffer for ``n_tokens`` tokens.

    The tile is ``ROW_TILE`` rows, or the power of two (at least 16, the
    bf16 sublane tile) at the mean group size where groups are smaller.
    Padding each of the ``E`` groups to whole tiles adds at most
    ``tile - 1`` rows to each, so ``n_tokens·k + E·(tile-1)`` rounded down
    to whole tiles always holds them."""
    m = cfg.moe
    pairs = n_tokens * m.top_k
    mean = -(-pairs // m.num_experts)
    tile = 16
    while tile < min(mean, ROW_TILE):
        tile *= 2
    return (pairs + m.num_experts * (tile - 1)) // tile * tile, tile


def _sort_pairs(idx, E: int, rows: int, tile: int):
    """Place each token-expert pair in the sorted, tile-padded buffer.

    idx: (T, k) expert of each pair.  Returns ``dest`` (T·k,), the buffer
    row of each pair (its expert's padded group start plus its rank among
    that expert's pairs, in token order: a cumulative count, no sort),
    and ``tile_ids`` (rows/tile,), the expert whose weights each row tile
    takes (tiles past the last group take expert E-1 over zero rows)."""
    e = idx.reshape(-1)
    one_hot = jax.nn.one_hot(e, E, dtype=jnp.int32)            # (T*k, E)
    counts = jnp.cumsum(one_hot, axis=0)
    rank = jnp.take_along_axis(counts - one_hot, e[:, None], axis=1)[:, 0]
    padded = (counts[-1] + tile - 1) // tile * tile
    ends_pad = jnp.cumsum(padded)
    dest = (ends_pad - padded)[e] + rank
    tile_ids = jnp.searchsorted(ends_pad, jnp.arange(rows // tile) * tile,
                                side="right")
    return dest, jnp.minimum(tile_ids, E - 1).astype(jnp.int32)


def _kernel_ok() -> bool:
    """The Pallas grouped matmul runs on a TPU outside any multi-device
    layout context."""
    from repro.runtime.sharding import layout_mesh
    mesh = layout_mesh()
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.devices.size == 1))


def _block_n(D: int, F: int) -> int:
    """Output columns per grid step: all ``F`` where a (D, F) bf16 weight
    slab fits in 4 MiB of VMEM (fewer grid steps), else the widest of
    512, 256, 128 that divides ``F`` and fits."""
    fits = lambda bn: F % bn == 0 and D * bn * 2 <= 4 << 20
    return next((bn for bn in (F, 512, 256, 128) if fits(bn)), 128)


def _grouped_jnp(x, w, tile_ids, tile: int):
    """The grouped product as one batched einsum over row tiles."""
    xt = x.reshape(tile_ids.shape[0], tile, x.shape[-1])
    y = jnp.einsum("tmd,tdf->tmf", xt, jnp.take(w, tile_ids, axis=0),
                   preferred_element_type=jnp.float32)
    return y.reshape(x.shape[0], w.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_kernel(x, w, tile_ids, tile: int):
    from repro.kernels.grouped_matmul.kernel import grouped_matmul_kernel
    return grouped_matmul_kernel(x, w, tile_ids, block_m=tile,
                                 block_n=_block_n(*w.shape[1:]),
                                 interpret=jax.default_backend() != "tpu")


def _grouped_kernel_fwd(x, w, tile_ids, tile):
    return _grouped_kernel(x, w, tile_ids, tile), (x, w, tile_ids)


def _grouped_kernel_bwd(tile, res, dy):
    """The kernel has no backward of its own: dx is the grouped product
    with the transposed weights, dw each tile's outer product summed into
    its expert."""
    x, w, tile_ids = res
    dx = _grouped_kernel(dy.astype(x.dtype), jnp.swapaxes(w, 1, 2),
                         tile_ids, tile)
    nt = tile_ids.shape[0]
    dw = jnp.einsum("tmd,tmf->tdf",
                    x.reshape(nt, tile, -1).astype(jnp.float32),
                    dy.reshape(nt, tile, -1))
    dw = jax.ops.segment_sum(dw, tile_ids, num_segments=w.shape[0])
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_grouped_kernel.defvjp(_grouped_kernel_fwd, _grouped_kernel_bwd)


def grouped_product(x, w, tile_ids, tile: int):
    """(rows, D) sorted rows x (E, D, F) expert weights -> (rows, F) f32,
    row tile ``i`` against expert ``tile_ids[i]``."""
    if _kernel_ok():
        return _grouped_kernel(x, w, tile_ids, tile)
    return _grouped_jnp(x, w, tile_ids, tile)


def apply_moe(x, p, cfg, compute=jnp.bfloat16):
    """Dropless MoE.  x: (B,S,D) -> (out (B,S,D), aux_loss scalar)."""
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.top_k
    probs = router_probs(x, p["router"])                       # (B,S,E) f32
    wts, idx = _top_k(probs, k)                                # (B,S,k)

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2), axis=(0, 1))
    aux = m.router_aux_weight * E * jnp.sum(me * ce / k)

    # dispatch per example, so a batch-sharded input sorts locally; the
    # examples' buffers are then one run of row tiles
    rows, tile = dropless_rows(S, cfg)
    dest, tile_ids = jax.vmap(
        lambda i: _sort_pairs(i, E, rows, tile))(idx)          # (B,S*k)
    # each buffer row's token; rows no pair lands in read the zero row S
    pair_tok = jnp.arange(S * k, dtype=jnp.int32) // k
    token = jax.vmap(lambda d: jnp.full((rows,), S, jnp.int32).at[d].set(
        pair_tok))(dest)                                       # (B,rows)
    xs = jax.vmap(lambda xe, te: jnp.take(
        jnp.concatenate([xe, jnp.zeros((1, D), xe.dtype)]), te, axis=0))(
            x, token)
    xs = constrain(xs, "b..").reshape(B * rows, D).astype(compute)
    tile_ids = tile_ids.reshape(-1)

    act = act_fn(cfg.activation)
    up = grouped_product(xs, p["up"].astype(compute), tile_ids, tile)
    if cfg.mlp_gated:
        g = grouped_product(xs, p["gate"].astype(compute), tile_ids, tile)
        h = act(g) * up
    else:
        h = act(up)
    h = constrain_replicated(h.astype(compute))
    y = grouped_product(h, p["down"].astype(compute), tile_ids, tile)
    y = jax.vmap(lambda ye, d: jnp.take(ye, d, axis=0))(
        y.reshape(B, rows, D), dest).reshape(B, S, k, D)       # f32 pairs
    out = jnp.einsum("bskd,bsk->bsd", y, wts)
    return constrain(out.astype(compute), "b.."), aux

def apply_moe_dense(x, p, cfg, compute=jnp.bfloat16):
    """Decode path: all experts on the (B,S,D) tokens, gated combine."""
    m = cfg.moe
    probs = router_probs(x, p["router"])                       # (B,S,E)
    wts, idx = _top_k(probs, m.top_k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None, None],
        jnp.arange(probs.shape[1])[None, :, None],
        idx,
    ].set(wts)                                                 # (B,S,E)

    act = act_fn(cfg.activation)
    up = jnp.einsum("bsd,edf->bsef", x, p["up"].astype(compute))
    if cfg.mlp_gated:
        g = jnp.einsum("bsd,edf->bsef", x, p["gate"].astype(compute))
        h = act(g) * up
    else:
        h = act(up)
    # serve-mode EP: experts over the (otherwise idle) data axis, expert
    # hidden over model — decode weight streaming drops by the data-axis
    # size; token activations are tiny so the reshard is ~free.
    h = constrain(h, "..dm")
    h = constrain_replicated(h)
    y = jnp.einsum("bsef,efd->bsed", h, p["down"].astype(compute))
    out = jnp.einsum("bsed,bse->bsd", y, gates.astype(compute))
    return constrain(out, "b.."), jnp.float32(0.0)
