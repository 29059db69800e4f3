"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

The state-space-duality form turns the selective scan into MXU work: within
a chunk of Q tokens everything is (Q,Q)/(Q,N)/(N,P) matmuls; only the
(N,P) running state crosses chunk boundaries.  Grid = (B, H, nChunks); the
chunk axis iterates sequentially on TPU so the state lives in VMEM scratch —
no HBM round-trip for the recurrence, which is the entire point of adapting
the GPU selective-scan to the TPU memory hierarchy.

Per-chunk math (all f32 in VMEM):
    dA    = dt * A_h                       (Q,)
    cum   = inclusive cumsum(dA)           (Q,)  as a triangular matmul
    L     = exp(cum_q - cum_j) masked to j<=q
    y     = ((C B^T) . L) @ (dt * x)       intra-chunk, (Q,P)
          + exp(cum) * (C @ state)         inter-chunk carry-in
    state = exp(cum_Q) * state + B^T @ (dt * exp(cum_Q - cum) * x)

VMEM tiling: Q=chunk (default 128) and N=state_dim (128) are lane-aligned;
P=head_dim (64) rides whole.  A arrives via scalar prefetch (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref,
                s_final_ref, state_sc, *, chunk, n_chunks):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_sc[...] = jnp.zeros_like(state_sc)

    A = a_ref[h]                                              # scalar
    x = x_ref[0, 0].astype(jnp.float32)                       # (Q,P)
    dt_r = dtr_ref[0, 0].astype(jnp.float32)                  # (1,Q)
    dt_c = dtc_ref[0, 0].astype(jnp.float32)                  # (Q,1)
    B = b_ref[0, 0].astype(jnp.float32)                       # (Q,N)
    C = c_ref[0, 0].astype(jnp.float32)                       # (Q,N)

    # inclusive cumsum of dA as a lower-triangular matmul, in row and
    # column form (the TPU lowering has no cumsum and no vector transpose
    # of a 1-D value)
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ji = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = qi >= ji
    tri = causal.astype(jnp.float32)                          # (Q,Q)
    hi = jax.lax.Precision.HIGHEST
    dA_r = dt_r * A                                           # (1,Q) <= 0
    dA_c = dt_c * A                                           # (Q,1)
    cum_c = jax.lax.dot_general(tri, dA_c, (((1,), (0,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)
    cum_r = jax.lax.dot_general(dA_r, tri, (((1,), (1,)), ((), ())),
                                precision=hi,
                                preferred_element_type=jnp.float32)
    total = jnp.sum(dA_r, axis=1, keepdims=True)              # (1,1)

    # ---- intra-chunk (Q,Q) masked decay matmul
    seg = jnp.where(causal, cum_c - cum_r, -jnp.inf)
    L = jnp.exp(seg)                                          # (Q,Q)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    xdt = x * dt_c                                            # (Q,P)
    y = jax.lax.dot_general(CB * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # ---- inter-chunk carry-in
    state = state_sc[...]                                     # (N,P)
    y += jnp.exp(cum_c) * jax.lax.dot_general(
        C, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # ---- state update
    decay_out = jnp.exp(total - cum_c)                        # (Q,1)
    S_loc = jax.lax.dot_general(
        B, x * (dt_c * decay_out),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # (N,P)
    state_sc[...] = jnp.exp(total) * state + S_loc

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_final_ref[0, 0] = state_sc[...]


def ssd_scan_kernel(x, dt, A, B, C, *, chunk=128, interpret=False):
    """x: (b,H,S,P); dt: (b,H,S); A: (H,); B,C: (b,G,S,N), H % G == 0.
    Returns (y (b,H,S,P) x.dtype, final_state (b,H,N,P) f32)."""
    b, H, S, P = x.shape
    G, N = B.shape[1], B.shape[3]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    rep = H // G

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci, a: (bi, h, ci, 0)),
            # dt twice: as a row (b,H,1,S) and as a column (b,H,S,1)
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bi, h, ci, a: (bi, h, 0, ci)),
            pl.BlockSpec((1, 1, chunk, 1),
                         lambda bi, h, ci, a: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bi, h, ci, a: (bi, h // rep, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda bi, h, ci, a: (bi, h // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bi, h, ci, a: (bi, h, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bi, h, ci, a: (bi, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((b, H, N, P), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), x, dt[:, :, None, :], dt[..., None], B, C)
