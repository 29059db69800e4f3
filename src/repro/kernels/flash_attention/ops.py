"""jit'd public wrapper for the flash-attention Pallas kernel.

Accepts model-layout tensors (q: (B,S,H,Dh); k/v: (B,T,K,Dh)), reshapes to
the kernel's GQA-grouped layout, and — when the mask is causal — clamps the
kv grid per q-block so fully-masked kv blocks are never launched (the
structural FLOP skip that the pure-JAX `chunked` path lacks).

On non-TPU backends the kernel runs in interpret mode (the Python body is
executed by the Pallas interpreter), which is exactly how the test suite
validates it against ref.py on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.runtime.mesh import MODEL_AXIS


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    block_q=128, block_k=128, interpret=None):
    """q: (B,S,H,Dh); k,v: (B,T,K,Dh) -> (B,S,H,Dh)."""
    B, S, H, Dh = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    if interpret is None:
        interpret = not _on_tpu()

    block_q = min(block_q, S)
    block_k = min(block_k, T)
    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    qg = q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4)   # (B,K,G,S,Dh)
    kg = k.transpose(0, 2, 1, 3)                              # (B,K,T,Dh)
    vg = v.transpose(0, 2, 1, 3)
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kg = jnp.pad(kg, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vg = jnp.pad(vg, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    o = flash_attention_kernel(qg, kg, vg, causal=causal, window=window,
                               q_offset=q_offset, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               t_total=T)
    o = o[:, :, :, :S]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh)


def flash_attention_tp(q, k, v, mesh, *, causal=True, window=None,
                       interpret=None):
    """Head-sharded :func:`flash_attention` under shard_map for the serve
    mesh: q (B,S,H,Dh) sharded on H, k/v (B,T,K,Dh) on K, output on H.  A
    Mosaic kernel cannot be partitioned automatically, so each shard runs
    the same kernel on its contiguous head slice — query head h attends kv
    head h // G, so a shard owns exactly the kv heads its queries read.
    Requires :func:`repro.runtime.mesh.tp_heads`."""
    if interpret is None:
        interpret = not _on_tpu()
    heads = P(None, None, MODEL_AXIS, None)
    fn = jax.shard_map(
        functools.partial(flash_attention, causal=causal, window=window,
                          interpret=interpret),
        mesh=mesh, in_specs=(heads, heads, heads), out_specs=heads,
        check_vma=False)
    return fn(q, k, v)
