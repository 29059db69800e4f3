"""Attention: GQA/MQA, sliding-window, MLA; train/prefill and decode paths.

Three implementations of the core attend step (selected by cfg.attn_impl):

* ``chunked``  — pure-JAX flash-style online softmax, lax.scan over KV chunks.
  Memory O(S·d + chunk) instead of O(S²); FLOPs equal to full attention
  (every (q,kv) chunk pair is computed, masked ones included).  This is the
  paper-faithful baseline path used by the dry-run.
* ``causal_blocked`` — beyond-paper compute optimization: static triangular
  iteration over (q-block, kv-block) pairs skips fully-masked kv blocks,
  halving causal-attention FLOPs (and bounding SWA to O(S·window)).
* ``pallas`` — TPU Pallas kernel (repro.kernels.flash_attention); validated
  in interpret mode on CPU, used on real TPU hardware.

Decode attends a single new token against a KV cache.  For ``long_500k``
(batch=1) the cache sequence dim is sharded over the "model" axis and the
softmax reductions become XLA-SPMD all-reduces — exactly flash-decode
split-K, derived by the partitioner instead of hand-written NCCL.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, dense_init, rmsnorm, rope_table
from repro.runtime.sharding import constrain, constrain_replicated

NEG_INF = -1e30


# ==========================================================================
# Parameter init
# ==========================================================================

def init_attention(key, cfg):
    if cfg.mla is not None:
        return _init_mla(key, cfg)
    D, H, K, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (D, H, Dh)),
        "wk": dense_init(ks[1], (D, K, Dh)),
        "wv": dense_init(ks[2], (D, K, Dh)),
        "wo": dense_init(ks[3], (H, Dh, D), in_axis=0),
    }


def _init_mla(key, cfg):
    s = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 6)
    return {
        "wq_a": dense_init(ks[0], (D, s.q_lora_rank)),
        "q_norm": jnp.zeros((s.q_lora_rank,), jnp.float32),
        "wq_b": dense_init(ks[1], (s.q_lora_rank, H, s.qk_head_dim)),
        "wkv_a": dense_init(ks[2], (D, s.kv_lora_rank + s.qk_rope_head_dim)),
        "kv_norm": jnp.zeros((s.kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[3], (s.kv_lora_rank, H, s.qk_nope_head_dim + s.v_head_dim)),
        "wo": dense_init(ks[4], (H, s.v_head_dim, D), in_axis=0),
    }


# ==========================================================================
# Core attend: (q, k, v) -> out, several implementations
# ==========================================================================

def _gqa_shapes(q, k):
    B, S, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    return B, S, H, K, G, Dh


def _mask_chunk(q_pos, t_pos, causal, window):
    """(S, Ck) boolean validity mask."""
    m = jnp.ones((q_pos.shape[0], t_pos.shape[0]), bool)
    if causal:
        m &= t_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= t_pos[None, :] > (q_pos[:, None] - window)
    return m


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      chunk=1024):
    """Flash-style online-softmax attention, scanning KV chunks.

    q: (B,S,H,Dh); k,v: (B,T,K,Dh).  q_offset: absolute position of q[0]
    (prefill continuation / blocked iteration).  Returns (B,S,H,Dh).
    """
    B, S, H, K, G, Dh = _gqa_shapes(q, k)
    T = k.shape[1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    scale = 1.0 / np.sqrt(Dh)

    qg = q.reshape(B, S, K, G, Dh).astype(jnp.bfloat16)
    kc = k.reshape(B, n_chunks, chunk, K, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, Dh).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry
        idx, kb, vb = inp
        t_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kb.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) * scale
        valid = _mask_chunk(q_pos, t_pos, causal, window)
        valid &= t_pos[None, :] < T            # padding
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(jnp.bfloat16),
                        vb.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l, acc), None

    init = (
        jnp.full((B, K, G, S), NEG_INF, jnp.float32),
        jnp.zeros((B, K, G, S), jnp.float32),
        jnp.zeros((B, K, G, S, Dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        body, init, (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh).astype(q.dtype)


def causal_blocked_attention(q, k, v, *, window=None, chunk=1024,
                             block_q=2048):
    """Triangular block iteration: q blocks are a static python loop, each
    attending only to its causal (and windowed) KV prefix.  Skips ~half the
    FLOPs of `chunked_attention` for causal masks; O(S·window) for SWA."""
    B, S, H, K, G, Dh = _gqa_shapes(q, k)
    T = k.shape[1]
    assert S == T, "blocked path is for self-attention (train/prefill)"
    block_q = min(block_q, S)
    if S % block_q:
        return chunked_attention(q, k, v, causal=True, window=window, chunk=chunk)
    outs = []
    for i in range(S // block_q):
        q_lo, q_hi = i * block_q, (i + 1) * block_q
        kv_lo = 0
        if window is not None:
            kv_lo = max(0, (q_lo - window + 1) // chunk * chunk)
        kv_hi = q_hi
        qb = q[:, q_lo:q_hi]
        kb = k[:, kv_lo:kv_hi]
        vb = v[:, kv_lo:kv_hi]
        # positions inside the block are q_lo..q_hi-1; kv starts at kv_lo.
        # chunked_attention masks with absolute positions via q_offset.
        outs.append(
            _chunked_attention_abs(qb, kb, vb, q_offset=q_lo, kv_offset=kv_lo,
                                   window=window, chunk=chunk))
    return jnp.concatenate(outs, axis=1)


def _chunked_attention_abs(q, k, v, *, q_offset, kv_offset, window, chunk):
    """chunked_attention with an absolute kv offset (for blocked iteration)."""
    B, S, H, K, G, Dh = _gqa_shapes(q, k)
    T = k.shape[1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    scale = 1.0 / np.sqrt(Dh)
    qg = q.reshape(B, S, K, G, Dh).astype(jnp.bfloat16)
    kc = k.reshape(B, n_chunks, chunk, K, Dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, K, Dh).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(S)

    def body(carry, inp):
        m, l, acc = carry
        idx, kb, vb = inp
        t_pos = kv_offset + idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kb.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32) * scale
        valid = _mask_chunk(q_pos, t_pos, True, window)
        valid &= t_pos[None, :] < kv_offset + T
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(jnp.bfloat16),
                        vb.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + pv
        return (m_new, l, acc), None

    init = (
        jnp.full((B, K, G, S), NEG_INF, jnp.float32),
        jnp.zeros((B, K, G, S), jnp.float32),
        jnp.zeros((B, K, G, S, Dh), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh).astype(q.dtype)


def attend(q, k, v, cfg, *, causal=True, window=None, q_offset=0):
    """Dispatch on cfg.attn_impl (self-attention, train/prefill)."""
    if cfg.attn_impl == "causal_blocked" and causal:
        return causal_blocked_attention(q, k, v, window=window,
                                        chunk=cfg.attn_chunk)
    if cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        from repro.runtime.mesh import tp_heads
        from repro.runtime.sharding import active_serve_mesh
        mesh = active_serve_mesh()
        if tp_heads(mesh, k.shape[2], q.shape[2]):
            return fa_ops.flash_attention_tp(q, k, v, mesh, causal=causal,
                                             window=window)
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, chunk=cfg.attn_chunk)


def decode_attend(q, k_cache, v_cache, cache_len, *, window=None):
    """Single-token attention against a KV cache.

    q: (B,1,H,Dh); caches: (B,T,K,Dh); cache_len: scalar or (B,) count of
    valid entries per row (continuous batching gives every batch row its own
    position, so the lengths are ragged).  With T sharded over "model", the
    max/sum reductions lower to all-reduces = flash-decode split-K via SPMD.
    """
    B, _, H, Dh = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(Dh)
    qg = q.reshape(B, K, G, Dh).astype(jnp.bfloat16)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * scale
    t_pos = jnp.arange(T)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
    valid = t_pos[None, :] < cl[:, None]                      # (B,T) ragged
    # Rolling SWA caches keep only the last `window` tokens, so every valid
    # slot is inside the window by construction; no extra masking needed.
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", p.astype(jnp.bfloat16),
                     v_cache.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


# ==========================================================================
# Full layer forward (projection + rope + attend + out-proj)
# ==========================================================================

def attention_forward(x, p, cfg, *, rope_cos, rope_sin, causal=True,
                      window=None, kv=None, compute=jnp.bfloat16):
    """Self- (kv=None) or cross- (kv=(k_in,)) attention over a full sequence.

    x: (B,S,D).  rope tables match S (None for cross-attention).
    """
    if cfg.mla is not None:
        return _mla_forward(x, p, cfg, rope_cos=rope_cos, rope_sin=rope_sin,
                            compute=compute)
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute)),
                  "b.m.")
    src = x if kv is None else kv
    k = constrain(jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(compute)),
                  "b.m.")
    v = constrain(jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(compute)),
                  "b.m.")
    if rope_cos is not None:
        q = apply_rope(q, rope_cos, rope_sin)
        k = apply_rope(k, rope_cos, rope_sin)
    out = constrain(attend(q, k, v, cfg, causal=causal, window=window),
                    "b.m.")
    return jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))


def _ring_write_full(k, v, cache, window=None):
    """Write a full prefill's k/v (B,S,K,Dh) into a (possibly rolling) cache
    (B,T,K,Dh), aligned so that slot = pos mod T."""
    S = k.shape[1]
    T = cache["k"].shape[1]
    if S <= T:
        kk = jnp.pad(k, ((0, 0), (0, T - S), (0, 0), (0, 0)))
        vv = jnp.pad(v, ((0, 0), (0, T - S), (0, 0), (0, 0)))
        return {"k": kk.astype(cache["k"].dtype), "v": vv.astype(cache["v"].dtype)}
    # keep the latest occupant of each ring slot: pos = S-1 - ((S-1-slot) mod T)
    slot_ids = jnp.arange(T)
    pos = (S - 1) - jnp.mod((S - 1) - slot_ids, T)
    kk = jnp.take(k, pos, axis=1).astype(cache["k"].dtype)
    vv = jnp.take(v, pos, axis=1).astype(cache["v"].dtype)
    return {"k": kk, "v": vv}


def attention_prefill(x, p, cfg, rope, cache, *, window=None,
                      compute=jnp.bfloat16):
    """Full-sequence self-attention that also fills the decode cache.

    Returns (out (B,S,D), new_cache)."""
    if cfg.mla is not None:
        return _mla_prefill(x, p, cfg, rope, cache, compute=compute)
    q = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute)),
                  "b.m.")
    k = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(compute)),
                  "b.m.")
    v = constrain(jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(compute)),
                  "b.m.")
    if rope[0] is not None:
        q = apply_rope(q, rope[0], rope[1])
        k = apply_rope(k, rope[0], rope[1])
    out = constrain(attend(q, k, v, cfg, causal=True, window=window), "b.m.")
    out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
    return out, _ring_write_full(k, v, cache, window)


def _mla_prefill(x, p, cfg, rope, cache, *, compute):
    """MLA prefill: full-expansion attention + compressed-latent cache fill."""
    s = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_project_q(x, p, cfg, compute)
    q_rope = apply_rope(q_rope, rope[0], rope[1])
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(compute))
    ckv = rmsnorm(kv_a[..., : s.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[:, :, None, s.kv_lora_rank:], rope[0], rope[1])
    kv = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"].astype(compute))
    k_nope = kv[..., : s.qk_nope_head_dim]
    v = kv[..., s.qk_nope_head_dim:]
    q = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "b.m.")
    k = constrain(jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, s.qk_rope_head_dim))],
        axis=-1), "b.m.")
    v_pad = constrain(jnp.pad(
        v, ((0, 0), (0, 0), (0, 0), (0, s.qk_head_dim - s.v_head_dim))),
        "b.m.")
    out = constrain(attend(q, k, v_pad, cfg, causal=True), "b.m.")
    out = out[..., : s.v_head_dim]
    out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
    T = cache["ckv"].shape[1]
    ckv_w = jnp.pad(ckv, ((0, 0), (0, T - S), (0, 0))) if S <= T else ckv[:, -T:]
    kr = k_rope[:, :, 0]
    kr_w = jnp.pad(kr, ((0, 0), (0, T - S), (0, 0))) if S <= T else kr[:, -T:]
    return out, {"ckv": ckv_w.astype(cache["ckv"].dtype),
                 "krope": kr_w.astype(cache["krope"].dtype)}


def _row_positions(pos, batch: int):
    """Normalize a decode position to the per-row (B,) form.  Scalar `pos`
    (every row at the same absolute position — the wave-era contract) is
    broadcast; a (B,) vector (continuous batching) passes through."""
    return jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))


# --------------------------------------------------------------------------
# Paged KV: block-pool writes and gathers
# --------------------------------------------------------------------------
#
# The paged cache is a shared pool ``(num_blocks, block_size, ...)`` plus a
# per-row block table ``(B, max_blocks)``: logical position ``p`` of row
# ``b`` lives at ``pool[table[b, p // bs], p % bs]``.  Block 0 is a
# reserved scratch block — free slots keep decoding over garbage (cheaper
# than masking the batched matmuls, same as the dense engine) and their
# writes land there, never in a live request's blocks.  The XLA fallback
# gathers each row's logical ``(max_blocks * bs,)`` view, which the
# allocator sizes to the engine ``max_len`` so the attend math (shapes,
# masks, reduction order) is bitwise-identical to the dense ring path.


def _paged_write_rows(pool, new, block_tables, pos):
    """Per-row paged write: pool (nb, bs, ...), new (B, 1, ...),
    block_tables (B, mb), pos (B,).  Row b's new entry lands at
    ``pool[table[b, (pos_b // bs) % mb], pos_b % bs]``."""
    bs = pool.shape[1]
    mb = block_tables.shape[1]
    pb = jnp.take_along_axis(
        block_tables, ((pos // bs) % mb)[:, None], axis=1)[:, 0]
    return pool.at[pb, pos % bs].set(new[:, 0].astype(pool.dtype))


def _paged_gather(pool, block_tables):
    """Materialize each row's logical view: (B, mb * bs, ...).  XLA
    fallback only — the Pallas kernel gathers via scalar prefetch.  One
    definition shared with the kernel oracle so the fallback and the
    oracle can never diverge."""
    from repro.kernels.paged_attention.ref import gather_kv
    return gather_kv(pool, block_tables)


def _paged_write_chunk(pool, new, table_row, positions):
    """Write a prefill chunk's rows for ONE batch row: pool (nb, bs, ...),
    new (C, ...), table_row (mb,), positions (C,) absolute."""
    bs = pool.shape[1]
    mb = table_row.shape[0]
    pb = table_row[(positions // bs) % mb]
    return pool.at[pb, positions % bs].set(new.astype(pool.dtype))


def _ring_write_rows(cache, new, slot):
    """Per-row ring-buffer write: cache (B,T,...), new (B,1,...), slot (B,).
    Each batch row lands at its own `pos mod T` — the vectorized form of the
    old scalar dynamic_update_slice."""
    upd = jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(c, n, s, axis=0))
    return upd(cache, new.astype(cache.dtype), slot)


def attention_decode(x, p, cfg, cache, pos, *, rope_theta=None,
                     window=None, block_tables=None, compute=jnp.bfloat16):
    """One decode step.  x: (B,1,D); cache {"k","v"}: (B,T,K,Dh) dense ring
    or {"kp","vp"}: (nb,bs,K,Dh) paged pool (then ``block_tables`` (B,mb)
    maps rows to blocks); pos: scalar or (B,) absolute position(s) of the
    new token — per-row positions are the continuous-batching path.
    Returns (out, new_cache)."""
    if cfg.mla is not None:
        return _mla_decode(x, p, cfg, cache, pos, block_tables=block_tables,
                           compute=compute)
    theta = rope_theta if rope_theta is not None else cfg.rope_theta
    B = x.shape[0]
    pos = _row_positions(pos, B)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(compute))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(compute))
    cos, sin = rope_table(pos[:, None], cfg.head_dim, theta)   # (B,1,dim/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if "kp" in cache:                       # paged block pool
        k_pool = _paged_write_rows(cache["kp"], k, block_tables, pos)
        v_pool = _paged_write_rows(cache["vp"], v, block_tables, pos)
        T = block_tables.shape[1] * k_pool.shape[1]
        cache_len = jnp.minimum(pos + 1, T)
        if cfg.attn_impl == "pallas":
            from repro.kernels.paged_attention.ops import (
                paged_decode_attention, paged_decode_attention_tp)
            from repro.runtime.mesh import tp_heads
            from repro.runtime.sharding import active_serve_mesh
            mesh = active_serve_mesh()
            if tp_heads(mesh, cfg.num_kv_heads, cfg.num_heads):
                out = paged_decode_attention_tp(q[:, 0], k_pool, v_pool,
                                                block_tables, cache_len,
                                                mesh)[:, None]
            else:
                out = paged_decode_attention(q[:, 0], k_pool, v_pool,
                                             block_tables, cache_len)[:, None]
        else:
            out = decode_attend(q, _paged_gather(k_pool, block_tables),
                                _paged_gather(v_pool, block_tables),
                                cache_len, window=window)
        out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
        return out, {"kp": k_pool, "vp": v_pool}
    T = cache["k"].shape[1]
    # per-row ring-buffer write (rolling for SWA; plain append when T >= max)
    slot = jnp.mod(pos, T)
    k_cache = _ring_write_rows(cache["k"], k, slot)
    v_cache = _ring_write_rows(cache["v"], v, slot)
    cache_len = jnp.minimum(pos + 1, T)
    if cfg.attn_impl == "pallas":
        from repro.kernels.decode_attention.ops import decode_attention
        out = decode_attention(q[:, 0], k_cache, v_cache,
                               cache_len)[:, None]
    else:
        out = decode_attend(q, k_cache, v_cache, cache_len, window=window)
    out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
    return out, {"k": k_cache, "v": v_cache}


def _paged_write_seq(pool, new, block_tables, pos):
    """Multi-position paged write for speculative verify: pool (nb, bs, ...),
    new (B, S, ...), block_tables (B, mb), pos (B,) base positions.  Row
    ``b``'s entry ``s`` lands at logical position ``pos_b + s``.  Unlike
    `_paged_write_rows` (which wraps the table index — safe for single-step
    decode because eviction fires before the wrap is reachable), positions
    at or past the table's logical capacity ``mb*bs`` are routed to the
    reserved scratch block 0 EXPLICITLY: a verify burst can run up to k
    positions past a row's end before acceptance clamps it, and those
    overflow writes must never land in a live (or prefix-shared) block."""
    bs = pool.shape[1]
    mb = block_tables.shape[1]
    S = new.shape[1]
    positions = pos[:, None] + jnp.arange(S)[None]             # (B, S)
    inb = positions < mb * bs
    blk = jnp.where(inb, positions // bs, 0)
    pb = jnp.where(inb, jnp.take_along_axis(block_tables, blk, axis=1), 0)
    return pool.at[pb, positions % bs].set(new.astype(pool.dtype))


def attention_verify(x, p, cfg, cache, pos, *, block_tables,
                     compute=jnp.bfloat16):
    """Speculative-verify attention: S = k+1 positions of every row in ONE
    forward.  x: (B,S,D); pos: (B,) absolute position of x[:,0]; paged
    cache only (the engine gates speculation to pure-paged archs).

    Writes the S new KV rows at ``pos..pos+S-1`` (overflow past the table's
    reach lands in the scratch block), then attends each query with its own
    causal frontier ``cache_len = pos+s+1``.  The XLA fallback is a static
    per-query loop through `decode_attend` — the exact shapes, masks and
    f32-softmax reduction order of a plain decode step — which is what
    makes accepted speculative tokens bitwise-equal to spec="off" greedy
    decode.  Returns (out (B,S,D), new_cache)."""
    if cfg.mla is not None:
        return _mla_verify(x, p, cfg, cache, pos, block_tables=block_tables,
                           compute=compute)
    if "kp" not in cache:
        raise ValueError("attention_verify requires a paged KV cache")
    B, S, _ = x.shape
    pos = _row_positions(pos, B)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(compute))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(compute))
    positions = pos[:, None] + jnp.arange(S)[None]             # (B, S)
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k_pool = _paged_write_seq(cache["kp"], k, block_tables, pos)
    v_pool = _paged_write_seq(cache["vp"], v, block_tables, pos)
    T = block_tables.shape[1] * k_pool.shape[1]
    if cfg.attn_impl == "pallas":
        from repro.kernels.paged_attention.ops import (
            paged_verify_attention, paged_verify_attention_tp)
        from repro.runtime.mesh import tp_heads
        from repro.runtime.sharding import active_serve_mesh
        mesh = active_serve_mesh()
        if tp_heads(mesh, cfg.num_kv_heads, cfg.num_heads):
            out = paged_verify_attention_tp(q, k_pool, v_pool, block_tables,
                                            pos, mesh)
        else:
            out = paged_verify_attention(q, k_pool, v_pool, block_tables, pos)
    else:
        kg = _paged_gather(k_pool, block_tables)
        vg = _paged_gather(v_pool, block_tables)
        out = jnp.concatenate(
            [decode_attend(q[:, s:s + 1], kg, vg,
                           jnp.minimum(pos + s + 1, T))
             for s in range(S)], axis=1)
    out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
    return out, {"kp": k_pool, "vp": v_pool}


def _mla_verify(x, p, cfg, cache, pos, *, block_tables, compute):
    """MLA speculative verify over the paged latent pools: per-query loop
    through `_mla_decode`'s absorbed-weight score math (same shapes, same
    masks, same reduction order — the bitwise-parity contract)."""
    s = cfg.mla
    if "ckvp" not in cache:
        raise ValueError("_mla_verify requires the paged latent pools")
    B, S, _ = x.shape
    pos = _row_positions(pos, B)
    q_nope, q_rope = _mla_project_q(x, p, cfg, compute)        # (B,S,H,*)
    positions = pos[:, None] + jnp.arange(S)[None]             # (B, S)
    cos, sin = rope_table(positions, s.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(compute))
    ckv_new = rmsnorm(kv_a[..., : s.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(kv_a[:, :, None, s.kv_lora_rank:], cos, sin)[:, :, 0]
    ckv_pool = _paged_write_seq(cache["ckvp"], ckv_new, block_tables, pos)
    kr_pool = _paged_write_seq(cache["kropep"], kr_new, block_tables, pos)
    ckv = constrain_replicated(_paged_gather(ckv_pool, block_tables))
    krope = constrain_replicated(_paged_gather(kr_pool, block_tables))
    T = ckv.shape[1]

    wkv_b = p["wkv_b"].astype(compute)                         # (r,H,n+v)
    wk = wkv_b[..., : s.qk_nope_head_dim]
    wv = wkv_b[..., s.qk_nope_head_dim:]
    scale = 1.0 / np.sqrt(s.qk_head_dim)
    outs = []
    for sq in range(S):
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, sq], wk)
        scores = (
            jnp.einsum("bhr,btr->bht", q_lat, ckv.astype(compute),
                       preferred_element_type=jnp.float32)
            + jnp.einsum("bhk,btk->bht", q_rope[:, sq], krope.astype(compute),
                         preferred_element_type=jnp.float32)
        ) * scale
        valid = (jnp.arange(T)[None]
                 < jnp.minimum(pos + sq + 1, T)[:, None])      # (B,T)
        scores = jnp.where(valid[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out_lat = jnp.einsum("bht,btr->bhr", probs.astype(compute),
                             ckv.astype(compute),
                             preferred_element_type=jnp.float32)
        out = jnp.einsum("bhr,rhv->bhv", out_lat.astype(compute), wv)
        outs.append(jnp.einsum("bhv,hvd->bd", constrain_replicated(out),
                               p["wo"].astype(compute))[:, None])
    return (jnp.concatenate(outs, axis=1),
            {"ckvp": ckv_pool, "kropep": kr_pool})


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-attention-layer cache pytree (SWA: rolling buffer of window)."""
    if cfg.mla is not None:
        s = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, s.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, s.qk_rope_head_dim), dtype),
        }
    T = max_len if cfg.sliding_window is None else min(max_len, cfg.sliding_window)
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, T, K, Dh), dtype),
        "v": jnp.zeros((batch, T, K, Dh), dtype),
    }


def init_kv_cache_paged(cfg, batch: int, max_len: int, num_blocks: int,
                        block_size: int, dtype=jnp.bfloat16):
    """Per-attention-layer PAGED cache: a shared block pool instead of a
    dense (batch, max_len) slab.  SWA layers keep the dense rolling ring —
    a window-sized ring is always fully live, so paging it saves nothing,
    and keeping it preserves bitwise decode parity with the dense path."""
    if cfg.sliding_window is not None and cfg.mla is None:
        return init_kv_cache(cfg, batch, max_len, dtype)
    if cfg.mla is not None:
        s = cfg.mla
        return {
            "ckvp": jnp.zeros((num_blocks, block_size, s.kv_lora_rank), dtype),
            "kropep": jnp.zeros((num_blocks, block_size, s.qk_rope_head_dim),
                                dtype),
        }
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    return {
        "kp": jnp.zeros((num_blocks, block_size, K, Dh), dtype),
        "vp": jnp.zeros((num_blocks, block_size, K, Dh), dtype),
    }


# ==========================================================================
# MLA (multi-head latent attention)
# ==========================================================================

def _mla_project_q(x, p, cfg, compute):
    s = cfg.mla
    ql = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(compute))
    ql = rmsnorm(ql, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", ql, p["wq_b"].astype(compute))
    return q[..., : s.qk_nope_head_dim], q[..., s.qk_nope_head_dim:]


def _mla_forward(x, p, cfg, *, rope_cos, rope_sin, compute):
    """Training / prefill MLA with full expansion."""
    s = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope = _mla_project_q(x, p, cfg, compute)
    q_rope = apply_rope(q_rope, rope_cos, rope_sin)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(compute))
    ckv, k_rope = kv_a[..., : s.kv_lora_rank], kv_a[..., s.kv_lora_rank:]
    ckv = rmsnorm(ckv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], rope_cos, rope_sin)  # (B,S,1,r)
    kv = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"].astype(compute))
    k_nope = kv[..., : s.qk_nope_head_dim]
    v = kv[..., s.qk_nope_head_dim:]

    q = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "b.m.")
    k = constrain(jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, s.qk_rope_head_dim))],
        axis=-1), "b.m.")
    # pad v head_dim up to qk_head_dim so the attend kernel sees square heads
    v_pad = constrain(jnp.pad(
        v, ((0, 0), (0, 0), (0, 0), (0, s.qk_head_dim - s.v_head_dim))),
        "b.m.")
    # the output constraint stops XLA sharding the score einsum's contraction
    # dim when H doesn't divide the model axis (minicpm3: 40 heads -> 10.6
    # TB/device of score all-reduces without this)
    out = constrain(attend(q, k, v_pad, cfg, causal=True), "b.m.")
    out = out[..., : s.v_head_dim]
    return jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))


def _mla_decode(x, p, cfg, cache, pos, *, block_tables=None, compute):
    """Absorbed-weight MLA decode over the compressed latent cache.

    Caches only (kv_lora + rope_dim) per token — the MLA memory win.  The
    score is computed directly in latent space:
        score = q_nope·W_kv_b^K·ckv + q_rope·k_rope
    The latent cache pages like any other: {"ckvp","kropep"} pools plus the
    shared block table replace the dense (B, T) slabs.
    """
    s = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    pos = _row_positions(pos, B)
    q_nope, q_rope = _mla_project_q(x, p, cfg, compute)          # (B,1,H,*)
    cos, sin = rope_table(pos[:, None], s.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(compute))
    ckv_new = rmsnorm(kv_a[..., : s.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(kv_a[:, :, None, s.kv_lora_rank:], cos, sin)[:, :, 0]

    if "ckvp" in cache:                     # paged latent pool
        ckv_pool = _paged_write_rows(cache["ckvp"], ckv_new, block_tables, pos)
        kr_pool = _paged_write_rows(cache["kropep"], kr_new, block_tables, pos)
        ckv = _paged_gather(ckv_pool, block_tables)
        krope = _paged_gather(kr_pool, block_tables)
        T = ckv.shape[1]
        new_cache = {"ckvp": ckv_pool, "kropep": kr_pool}
    else:
        T = cache["ckv"].shape[1]
        slot = jnp.mod(pos, T)
        ckv = _ring_write_rows(cache["ckv"], ckv_new, slot)
        krope = _ring_write_rows(cache["krope"], kr_new, slot)
        new_cache = None                    # filled below (dense returns full)

    # serve TP: the latent pools shard on r — gather the rows whole so the
    # score/out contractions over r keep single-device reduction order
    ckv = constrain_replicated(ckv)
    krope = constrain_replicated(krope)
    wkv_b = p["wkv_b"].astype(compute)                           # (r,H,n+v)
    wk = wkv_b[..., : s.qk_nope_head_dim]                        # (r,H,n)
    wv = wkv_b[..., s.qk_nope_head_dim:]                         # (r,H,v)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wk)         # absorb
    scale = 1.0 / np.sqrt(s.qk_head_dim)
    scores = (
        jnp.einsum("bhr,btr->bht", q_lat, ckv.astype(compute),
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhk,btk->bht", q_rope[:, 0], krope.astype(compute),
                     preferred_element_type=jnp.float32)
    ) * scale
    valid = jnp.arange(T)[None] < jnp.minimum(pos + 1, T)[:, None]   # (B,T)
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bht,btr->bhr", probs.astype(compute),
                         ckv.astype(compute),
                         preferred_element_type=jnp.float32)     # (B,H,r)
    out = jnp.einsum("bhr,rhv->bhv", out_lat.astype(compute), wv)
    out = jnp.einsum("bhv,hvd->bd", constrain_replicated(out), p["wo"].astype(compute))[:, None]
    return out, (new_cache if new_cache is not None
                 else {"ckv": ckv, "krope": krope})


# ==========================================================================
# Chunked prefill (paged serve path)
# ==========================================================================
#
# Admission prefill split into fixed-size chunks so running slots never see
# a stop-the-world prefill: each chunk writes its KV into the admitted
# row's blocks, then attends against everything cached so far (earlier
# chunks included) with a causal mask on absolute positions.  One batch
# row at a time — the other rows' decode state is untouched.


def _chunk_attend(q, k, v, q_pos, t_pos=None, window=None):
    """Causal attention of a prefill chunk against gathered cache KV.

    q: (1,C,H,Dh); k,v: (1,T,K,Dh); q_pos: (C,) absolute query positions;
    t_pos: (T,) absolute key positions (default 0..T-1; negatives are
    invalid — SWA pre-window slots).  f32 softmax like `decode_attend`.
    """
    B, C, H, Dh = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / np.sqrt(Dh)
    if t_pos is None:
        t_pos = jnp.arange(T)
    qg = q.reshape(B, C, K, G, Dh).astype(jnp.bfloat16)
    s = jnp.einsum("bckgd,btkd->bkgct", qg, k.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) * scale
    valid = (t_pos[None, :] <= q_pos[:, None]) & (t_pos[None, :] >= 0)
    if window is not None:
        valid &= t_pos[None, :] > (q_pos[:, None] - window)
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,btkd->bckgd", p.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, Dh).astype(q.dtype)


def _ring_write_chunk_row(row, chunk, q_offset):
    """Write a chunk (C, ...) into one ring row (W, ...) keeping, per ring
    slot, the LATEST position ≤ q_offset+C-1 (deterministic gather-form of
    the rolling write; safe for any chunk/window ratio)."""
    W = row.shape[0]
    C = chunk.shape[0]
    r = jnp.arange(W)
    last = q_offset + C - 1
    p = last - jnp.mod(last - r, W)              # latest pos ≡ r (mod W)
    take = p >= q_offset
    src = jnp.take(chunk, jnp.clip(p - q_offset, 0, C - 1), axis=0)
    return jnp.where(
        jnp.reshape(take, (W,) + (1,) * (row.ndim - 1)),
        src.astype(row.dtype), row)


def attention_prefill_chunk(x, p, cfg, cache, table_row, slot, q_offset,
                            *, window=None, compute=jnp.bfloat16):
    """One prefill chunk of ONE batch row.  x: (1,C,D); cache: the full
    engine cache leaf (paged pools, or a dense SWA ring); table_row: (mb,)
    int32 physical block ids of the admitted row (passed explicitly — the
    engine installs the row into the shared block table only once the
    LAST chunk lands, so free-slot garbage writes keep hitting the scratch
    block mid-admission); slot: scalar int32 batch row; q_offset: scalar
    int32 absolute position of x[:,0].  Returns (out (1,C,D), new_cache)."""
    if cfg.mla is not None:
        return _mla_prefill_chunk(x, p, cfg, cache, table_row, slot,
                                  q_offset, compute=compute)
    C = x.shape[1]
    positions = q_offset + jnp.arange(C)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(compute))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(compute))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(compute))
    cos, sin = rope_table(positions[None], cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if "kp" in cache:                        # full attention: paged pool
        k_pool = _paged_write_chunk(cache["kp"], k[0], table_row, positions)
        v_pool = _paged_write_chunk(cache["vp"], v[0], table_row, positions)
        kg = _paged_gather(k_pool, table_row[None])      # (1,T,K,Dh)
        vg = _paged_gather(v_pool, table_row[None])
        out = _chunk_attend(q, kg, vg, positions)
        new_cache = {"kp": k_pool, "vp": v_pool}
    else:                                    # SWA: dense rolling ring
        W = cache["k"].shape[1]
        k_row = jax.lax.dynamic_index_in_dim(cache["k"], slot, 0, False)
        v_row = jax.lax.dynamic_index_in_dim(cache["v"], slot, 0, False)
        # chronological snapshot of the last W cached positions BEFORE the
        # chunk writes over them (ring slot of position p is p mod W)
        p_prev = q_offset - W + jnp.arange(W)
        k_prev = jnp.take(k_row, jnp.mod(p_prev, W), axis=0)
        v_prev = jnp.take(v_row, jnp.mod(p_prev, W), axis=0)
        k_all = jnp.concatenate([k_prev[None], k], axis=1)   # (1,W+C,K,Dh)
        v_all = jnp.concatenate([v_prev[None], v], axis=1)
        t_pos = jnp.concatenate([p_prev, positions])
        out = _chunk_attend(q, k_all, v_all, positions, t_pos=t_pos,
                            window=window)
        new_k = _ring_write_chunk_row(k_row, k[0], q_offset)
        new_v = _ring_write_chunk_row(v_row, v[0], q_offset)
        new_cache = {
            "k": jax.lax.dynamic_update_index_in_dim(
                cache["k"], new_k.astype(cache["k"].dtype), slot, 0),
            "v": jax.lax.dynamic_update_index_in_dim(
                cache["v"], new_v.astype(cache["v"].dtype), slot, 0),
        }
    out = jnp.einsum("bshk,hkd->bsd", constrain_replicated(out), p["wo"].astype(compute))
    return out, new_cache


def _mla_prefill_chunk(x, p, cfg, cache, table_row, slot, q_offset, *,
                       compute):
    """Chunked MLA prefill via the absorbed-weight latent score (same math
    as `_mla_decode`, vectorized over the chunk's C query positions)."""
    s = cfg.mla
    B, C, _ = x.shape
    positions = q_offset + jnp.arange(C)
    q_nope, q_rope = _mla_project_q(x, p, cfg, compute)      # (1,C,H,*)
    cos, sin = rope_table(positions[None], s.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(compute))
    ckv_new = rmsnorm(kv_a[..., : s.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    kr_new = apply_rope(kv_a[:, :, None, s.kv_lora_rank:], cos, sin)[:, :, 0]

    ckv_pool = _paged_write_chunk(cache["ckvp"], ckv_new[0], table_row,
                                  positions)
    kr_pool = _paged_write_chunk(cache["kropep"], kr_new[0], table_row,
                                 positions)
    ckv = constrain_replicated(
        _paged_gather(ckv_pool, table_row[None]))            # (1,T,r)
    krope = constrain_replicated(_paged_gather(kr_pool, table_row[None]))
    T = ckv.shape[1]

    wkv_b = p["wkv_b"].astype(compute)                       # (r,H,n+v)
    wk = wkv_b[..., : s.qk_nope_head_dim]
    wv = wkv_b[..., s.qk_nope_head_dim:]
    q_lat = jnp.einsum("bchn,rhn->bchr", q_nope, wk)
    scale = 1.0 / np.sqrt(s.qk_head_dim)
    scores = (
        jnp.einsum("bchr,btr->bhct", q_lat, ckv.astype(compute),
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bchk,btk->bhct", q_rope, krope.astype(compute),
                     preferred_element_type=jnp.float32)
    ) * scale
    valid = jnp.arange(T)[None, :] <= positions[:, None]     # (C,T)
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out_lat = jnp.einsum("bhct,btr->bchr", probs.astype(compute),
                         ckv.astype(compute),
                         preferred_element_type=jnp.float32)
    out = jnp.einsum("bchr,rhv->bchv", out_lat.astype(compute), wv)
    out = jnp.einsum("bchv,hvd->bcd", constrain_replicated(out), p["wo"].astype(compute))
    return out, {"ckvp": ckv_pool, "kropep": kr_pool}
