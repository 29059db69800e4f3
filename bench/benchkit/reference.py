"""Plain float32 reference of a decoder-only LM, read from a config file.

Imports nothing of the program under test.  Every size and scalar comes
from the configuration's ``model`` dict (Hugging Face key names), so a
published multiplier the program leaves out is computed here all the same.

Weights are made from the seed by the same recipe the serve image states
for its random weights (``weights`` in the config file): a root key
``jax.random.key(seed)`` split four ways (embedding, head, final norm,
layers), one key per layer, truncated-normal fan-in init for every
projection and normal(0.02) for the embedding.  RMSNorm gains are stored
as ``gain - 1`` and start at 0.  Layer ``l``'s weights are made on demand,
so the forward runs layer by layer and never holds more than one layer
beside the activations.

``low="fp8"`` is the control: every tensor the serve path holds in
bfloat16 (weights and inputs of every projection, q, k, v and the
attention weights, the residual stream) is rounded to float8 e4m3, the
precision below bfloat16, and products still accumulate in float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    experts: int
    top_k: int
    rope_theta: float
    eps: float
    tied: bool
    emb_mult: float
    attn_scale: float
    res_mult: float
    logits_div: float

    @classmethod
    def from_config(cls, m: dict) -> "Model":
        d, heads = int(m["hidden_size"]), int(m["num_attention_heads"])
        head_dim = int(m.get("head_dim") or d // heads)
        return cls(
            layers=int(m["num_hidden_layers"]), d=d, heads=heads,
            kv_heads=int(m["num_key_value_heads"]), head_dim=head_dim,
            d_ff=int(m["intermediate_size"]), vocab=int(m["vocab_size"]),
            experts=int(m.get("num_local_experts", 0)),
            top_k=int(m.get("num_experts_per_tok", 0)),
            rope_theta=float(m.get("rope_theta", 10000.0)),
            eps=float(m.get("rms_norm_eps", 1e-5)),
            tied=bool(m.get("tie_word_embeddings", False)),
            emb_mult=float(m.get("embedding_multiplier", 1.0)),
            attn_scale=float(m.get("attention_multiplier",
                                   1.0 / np.sqrt(head_dim))),
            res_mult=float(m.get("residual_multiplier", 1.0)),
            logits_div=float(m.get("logits_scaling", 1.0)))


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def _dense(key, shape, in_axis=0):
    std = 1.0 / np.sqrt(shape[in_axis])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(jnp.float32)


def root_keys(seed: int, n_layers: int):
    """(embed key, head key, per-layer keys (n_layers,))."""
    k_embed, k_head, _k_norm, k_layers = jax.random.split(
        jax.random.key(seed), 4)
    return k_embed, k_head, jax.random.split(k_layers, (n_layers, 1))[:, 0]


@functools.partial(jax.jit, static_argnums=0)
def layer_weights(m: Model, key) -> dict:
    ks = jax.random.split(key, 4)
    ka = jax.random.split(ks[1], 4)
    D, H, K, Dh = m.d, m.heads, m.kv_heads, m.head_dim
    w = {"ln1": jnp.zeros((D,), jnp.float32),
         "ln2": jnp.zeros((D,), jnp.float32),
         "wq": _dense(ka[0], (D, H, Dh)), "wk": _dense(ka[1], (D, K, Dh)),
         "wv": _dense(ka[2], (D, K, Dh)), "wo": _dense(ka[3], (H, Dh, D))}
    if m.experts:
        E, F = m.experts, m.d_ff
        kf = jax.random.split(ks[3], 4)
        w.update(router=_dense(kf[0], (D, E)),
                 up=_dense(kf[1], (E, D, F), 1),
                 down=_dense(kf[2], (E, F, D), 1),
                 gate=_dense(kf[3], (E, D, F), 1))
    else:
        kf = jax.random.split(ks[3], 3)
        w.update(up=_dense(kf[0], (D, m.d_ff)), down=_dense(kf[1], (m.d_ff, D)),
                 gate=_dense(kf[2], (D, m.d_ff)))
    return w


@functools.partial(jax.jit, static_argnums=0)
def outer_weights(m: Model, k_embed, k_head) -> dict:
    w = {"embed": jax.random.normal(k_embed, (m.vocab, m.d), jnp.float32)
         * 0.02,
         "final": jnp.zeros((m.d,), jnp.float32)}
    if not m.tied:
        w["head"] = _dense(k_head, (m.d, m.vocab))
    return w


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

LOW = {None: None, "fp8": jnp.float8_e4m3fn}


def _r(x, low):
    """``x`` rounded to the precision ``low`` names (as float32)."""
    return x if low is None else x.astype(LOW[low]).astype(jnp.float32)


def _mm(spec: str, x, w, low):
    return jnp.einsum(spec, _r(x, low), _r(w, low), precision="highest")


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g)


def _rope(x, pos, theta):
    dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[:, None] * freqs            # (S, dim/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(m: Model, w: dict, x, low):
    B, S, D = x.shape
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], m.eps)
    q = _r(_rope(_mm("bsd,dhk->bshk", h, w["wq"], low), pos, m.rope_theta),
           low)
    k = _r(_rope(_mm("bsd,dhk->bshk", h, w["wk"], low), pos, m.rope_theta),
           low)
    v = _r(_mm("bsd,dhk->bshk", h, w["wv"], low), low)
    G = m.heads // m.kv_heads
    qg = q.reshape(B, S, m.kv_heads, G, m.head_dim)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k, precision="highest")
    s = s * m.attn_scale
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = _r(jax.nn.softmax(s, axis=-1), low)
    o = jnp.einsum("bkgqt,btkd->bqkgd", p, v, precision="highest")
    o = _mm("bshk,hkd->bsd", o.reshape(B, S, m.heads, m.head_dim), w["wo"],
            low)
    x = _r(x + m.res_mult * o, low)
    h = _rms(x, w["ln2"], m.eps)
    if m.experts:
        logits = jnp.einsum("bsd,de->bse", h, w["router"],
                            precision="highest")
        probs = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(probs, m.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, m.experts) * top[..., None], -2)
        up = _mm("bsd,edf->bsef", h, w["up"], low)
        gt = _mm("bsd,edf->bsef", h, w["gate"], low)
        y = _mm("bsef,efd->bsed", jax.nn.silu(gt) * up, w["down"], low)
        f = jnp.einsum("bsed,bse->bsd", y, gates, precision="highest")
    else:
        up = _mm("bsd,df->bsf", h, w["up"], low)
        gt = _mm("bsd,df->bsf", h, w["gate"], low)
        f = _mm("bsf,fd->bsd", jax.nn.silu(gt) * up, w["down"], low)
    return _r(x + m.res_mult * f, low)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _head(m: Model, w: dict, x, low):
    h = _rms(x, w["final"], m.eps)
    head = w["embed"].T if m.tied else w["head"]
    return _mm("bsd,dv->bsv", h, head, low) / m.logits_div


def forward(m: Model, seed: int, tokens, *, low: str | None = None):
    """Logits (B, S, V) float32 for token ids (B, S), causal from 0."""
    k_embed, k_head, lkeys = root_keys(seed, m.layers)
    outer = outer_weights(m, k_embed, k_head)
    x = jnp.take(outer["embed"], jnp.asarray(tokens), axis=0) * m.emb_mult
    x = _r(x, low)
    for layer in range(m.layers):
        x = _layer(m, layer_weights(m, lkeys[layer]), x, low)
    return _head(m, outer, x, low)
