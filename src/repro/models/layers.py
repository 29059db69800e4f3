"""Shared building blocks: norms, RoPE, MLPs, embeddings, losses.

Everything is a pure function over explicit param pytrees (no flax).  Params
are stored in ``param_dtype`` (f32 for training, bf16 for serving) and cast to
``compute_dtype`` at the point of use; reductions that need precision (norm
variance, softmax, loss) run in f32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.sharding import constrain_replicated


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    """Truncated-normal fan-in init (matches maxtext/llama defaults)."""
    fan_in = shape[in_axis]
    std = 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def init_norm(key, cfg, d: int | None = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}   # rmsnorm stores (scale-1)


def apply_norm(x, p, cfg):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    if getattr(cfg, "norm_impl", "jnp") == "pallas":
        from repro.kernels.rmsnorm.ops import rmsnorm_fused
        return rmsnorm_fused(x, p["scale"], eps=cfg.norm_eps)[0]
    return rmsnorm(x, p["scale"], cfg.norm_eps)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_table(positions, dim: int, theta: float):
    """cos/sin tables for `positions` (any shape) and head sub-dim `dim`."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs     # (..., dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., seq, heads, dim); cos/sin: (seq, dim/2), (B, seq, dim/2)
    (per-row positions — continuous-batching decode), or broadcastable."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:      # (S, dim/2) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    elif cos.ndim == 3:    # (B, S, dim/2) -> broadcast over heads
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def init_mlp(key, cfg):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], (D, F)), "down": dense_init(ks[1], (F, D))}
    if cfg.mlp_gated:
        p["gate"] = dense_init(ks[2], (D, F))
    return p


def apply_mlp(x, p, cfg, compute_dtype=jnp.bfloat16):
    act = act_fn(cfg.activation)
    up = jnp.einsum("bsd,df->bsf", x, p["up"].astype(compute_dtype))
    if cfg.mlp_gated:
        gate = jnp.einsum("bsd,df->bsf", x, p["gate"].astype(compute_dtype))
        h = act(gate) * up
    else:
        h = act(up)
    # serve TP: h is d_ff-sharded (up/gate column-parallel); gather it so
    # the down contraction keeps single-device reduction order
    h = constrain_replicated(h)
    return jnp.einsum("bsf,fd->bsd", h, p["down"].astype(compute_dtype))


# --------------------------------------------------------------------------
# Embedding / head / loss
# --------------------------------------------------------------------------

def embed_lookup(tokens, table, compute_dtype=jnp.bfloat16):
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)


def lm_logits(x, head, softcap: float | None = None, scaling: float = 1.0):
    """x: (B,S,D) compute dtype; head: (D,V).  Returns f32 logits, divided
    by ``scaling`` (the published logits scaling; no op at 1)."""
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype)).astype(jnp.float32)
    if scaling != 1.0:
        logits = logits / scaling
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    # serve TP: head is vocab-sharded (column-parallel); gather so the
    # engine's argmax/top-k run on replicated logits
    return constrain_replicated(logits)


def softmax_cross_entropy(logits, targets, mask=None):
    """logits (B,S,V) f32, targets (B,S) int32 -> scalar mean loss."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def softmax_cross_entropy_fused(h, head, targets, *, softcap=None, mask=None,
                                chunk: int = 1024, scaling: float = 1.0):
    """Mean CE of ``logits = h @ head`` WITHOUT materializing (B,S,V).

    The full logits tensor is the single largest intermediate of an LM train
    step (gemma train_4k: 256x4096x256000 f32 = 1 PB global).  We scan the
    sequence in `chunk`-token slices: each slice's (B,c,V) logits is a scan
    temporary, and jax.checkpoint on the body recomputes it in backward, so
    peak memory is one chunk instead of the whole sequence.  With the head's
    V dim sharded over "model" and B over ("pod","data"), the per-chunk
    logsumexp lowers to one small all-reduce per chunk.

    h: (B,S,D) compute dtype; head: (D,V); targets: (B,S) int32.
    """
    B, S, D = h.shape
    if S <= chunk:
        return softmax_cross_entropy(lm_logits(h, head, softcap, scaling),
                                     targets, mask)
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = h.shape[1] // chunk
    hc = jnp.moveaxis(h.reshape(B, nc, chunk, D), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nc, chunk), 1, 0)
    mc = jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        from repro.runtime.sharding import constrain
        tot, cnt = carry
        hb, tb, mb = inp
        hb = constrain(hb, "b..")
        logits = lm_logits(hb, head, softcap, scaling)   # (B,c,V) temporary
        logits = constrain(logits, "b.m")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mb
        return (tot + jnp.sum(nll), cnt + jnp.sum(mb)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (hc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)
