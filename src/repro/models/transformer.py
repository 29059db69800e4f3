"""Unified decoder-only LM covering dense / MoE / SSM / hybrid / VLM archs.

Layers are organized into *groups* of ``period`` layers, where
``period = lcm(attn_period, moe_period)`` (1 for uniform stacks, 8 for
jamba's 1:7 mamba:attn interleave with alternating MoE).  Parameters are
stacked per slot across groups and the group is the body of a
``jax.lax.scan`` — compile time and HLO size stay O(period), not O(L),
which keeps the 80-cell dry-run tractable and is how the framework holds
compile latency down in production (late-binding's "image pull" cost).

Caches (decode) are pytrees stacked the same way and threaded through the
scan as per-iteration xs/ys.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    apply_mlp, apply_norm, embed_init, embed_lookup, init_mlp, init_norm,
    lm_logits, rope_table, softmax_cross_entropy_fused,
)
from repro.runtime.sharding import constrain


# --------------------------------------------------------------------------
# Layer-slot layout
# --------------------------------------------------------------------------

def group_period(cfg) -> int:
    p = 1
    if cfg.ssm is not None and not cfg.is_attention_free:
        p = math.lcm(p, cfg.attn_period)
    if cfg.moe is not None:
        p = math.lcm(p, cfg.moe_period)
    return p


def layer_slots(cfg) -> list[dict]:
    """Static per-slot structure within one group."""
    period = group_period(cfg)
    assert cfg.num_layers % period == 0, (cfg.name, cfg.num_layers, period)
    attn_set = set(i % period for i in cfg.attn_layer_indices() if i < period)
    moe_set = set(i % period for i in cfg.moe_layer_indices() if i < period)
    slots = []
    for i in range(period):
        if cfg.is_attention_free:
            mixer = "ssm"
        else:
            mixer = "attn" if (cfg.ssm is None or i in attn_set) else "ssm"
        if cfg.moe is not None and i in moe_set:
            ffn = "moe"
        elif cfg.d_ff > 0:
            ffn = "dense"
        else:
            ffn = "none"
        slots.append({"mixer": mixer, "ffn": ffn})
    return slots


def _rope_for(cfg, S):
    if cfg.is_attention_free:
        return (None, None)
    dim = cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.head_dim
    return rope_table(jnp.arange(S), dim, cfg.rope_theta)


# The published scalars (``embedding_multiplier``, ``residual_multiplier``,
# ``logits_scaling``; the attention multiplier lives in `attention.scale_q`)
# are skipped in Python where they are 1, so a model without them traces
# exactly the ops it did before they existed.

def _embed(params, cfg, tokens, compute):
    """Token embeddings in ``compute``, times the embedding multiplier."""
    if cfg.embedding_multiplier == 1.0:
        return embed_lookup(tokens, params["embed"], compute)
    x = jnp.take(params["embed"], tokens, axis=0)
    return (x * cfg.embedding_multiplier).astype(compute)


def _residual(x, h, cfg):
    """``x + h``, the branch ``h`` scaled by the residual multiplier."""
    if cfg.residual_multiplier == 1.0:
        return x + h
    return x + h * cfg.residual_multiplier


def _logits(x, params, cfg):
    return lm_logits(x, head_matrix(params, cfg), cfg.logit_softcap,
                     cfg.logits_scaling)


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------

def _init_slot(key, cfg, slot):
    ks = jax.random.split(key, 4)
    p = {"mixer_norm": init_norm(ks[0], cfg)}
    if slot["mixer"] == "attn":
        p["mixer"] = attn.init_attention(ks[1], cfg)
    else:
        p["mixer"] = ssm_mod.init_ssm(ks[1], cfg)
    if slot["ffn"] != "none":
        p["ffn_norm"] = init_norm(ks[2], cfg)
        p["ffn"] = (init_mlp(ks[3], cfg) if slot["ffn"] == "dense"
                    else moe_mod.init_moe(ks[3], cfg))
    return p


@functools.partial(jax.jit, static_argnums=0)
def _init_layers(cfg, layer_keys):
    """The stacked layer leaves, made one group at a time: a staged init
    holds one group's random bits at once, where a ``vmap`` over the groups
    holds every layer's.  Jitted on ``cfg``: run eagerly, ``lax.map``
    would trace and compile its loop on every call."""
    period = group_period(cfg)
    slots = layer_slots(cfg)

    def init_group(gkeys):
        return [_init_slot(gkeys[i], cfg, slots[i]) for i in range(period)]

    return jax.lax.map(init_group, layer_keys)


def init_lm_params(cfg, key):
    """Full parameter pytree; layer leaves have leading dim n_groups."""
    period = group_period(cfg)
    n_groups = cfg.num_layers // period
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    layer_keys = jax.random.split(k_layers, (n_groups, period))
    params = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model)),
        "layers": _init_layers(cfg, layer_keys),
        "final_norm": init_norm(k_norm, cfg),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size))
    return params


# --------------------------------------------------------------------------
# Forward (train)
# --------------------------------------------------------------------------

def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _apply_slot(x, p, cfg, slot, rope, compute):
    aux = jnp.float32(0.0)
    h = apply_norm(x, p["mixer_norm"], cfg)
    if slot["mixer"] == "attn":
        h = attn.attention_forward(
            h, p["mixer"], cfg, rope_cos=rope[0], rope_sin=rope[1],
            causal=True, window=cfg.sliding_window, compute=compute)
    else:
        h = ssm_mod.ssm_forward(h, p["mixer"], cfg, compute=compute)
    x = _residual(x, h, cfg)
    if slot["ffn"] != "none":
        h = apply_norm(x, p["ffn_norm"], cfg)
        if slot["ffn"] == "dense":
            h = apply_mlp(h, p["ffn"], cfg, compute)
        else:
            h, aux = moe_mod.apply_moe(h, p["ffn"], cfg, compute)
        x = _residual(x, h, cfg)
    return x, aux


def lm_backbone(params, cfg, x, *, compute=jnp.bfloat16):
    """Run the layer stack over embeddings x: (B,S,D) -> (hidden, aux_loss)."""
    slots = layer_slots(cfg)
    rope = _rope_for(cfg, x.shape[1])

    def group_body(carry, gparams):
        x, aux = carry
        # re-pin the batch sharding: XLA drops it in the grad(remat(scan))
        # backward loop otherwise (see runtime.sharding.constrain)
        x = constrain(x, "b..")
        for i, slot in enumerate(slots):
            x, a = _apply_slot(x, gparams[i], cfg, slot, rope, compute)
            aux = aux + a
        x = constrain(x, "b..")
        return (x, aux), None

    body = _remat(group_body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = apply_norm(x, params["final_norm"], cfg)
    return x, aux


def head_matrix(params, cfg):
    return params["embed"].T if cfg.tie_embeddings else params["head"]


def lm_loss(params, cfg, tokens, targets, *, extra_embeds=None,
            loss_mask=None, compute=jnp.bfloat16):
    """Next-token CE loss.  extra_embeds (B,F,D) are prepended (VLM/audio
    stub frontends); the loss covers token positions only."""
    x = _embed(params, cfg, tokens, compute)
    n_extra = 0
    if extra_embeds is not None:
        n_extra = extra_embeds.shape[1]
        x = jnp.concatenate([extra_embeds.astype(compute), x], axis=1)
    h, aux = lm_backbone(params, cfg, x, compute=compute)
    h = h[:, n_extra:]
    ce = softmax_cross_entropy_fused(
        h, head_matrix(params, cfg), targets,
        softcap=cfg.logit_softcap, mask=loss_mask, chunk=cfg.loss_chunk,
        scaling=cfg.logits_scaling)
    return ce + aux, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# Prefill / decode with caches
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-group cache pytree: list per slot, leaves (n_groups, ...)."""
    period = group_period(cfg)
    n_groups = cfg.num_layers // period
    slots = layer_slots(cfg)

    def one(slot):
        if slot["mixer"] == "attn":
            c = attn.init_kv_cache(cfg, batch, max_len, dtype)
        else:
            c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), c)

    return [one(s) for s in slots]


def init_cache_paged(cfg, batch: int, max_len: int, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16):
    """Paged variant of `init_cache`: attention layers allocate a shared
    block pool (n_groups, num_blocks, block_size, ...) instead of a dense
    (n_groups, batch, max_len, ...) slab; SSM state and SWA rings stay
    per-row (they are O(1) / always-live respectively)."""
    period = group_period(cfg)
    n_groups = cfg.num_layers // period
    slots = layer_slots(cfg)

    def one(slot):
        if slot["mixer"] == "attn":
            c = attn.init_kv_cache_paged(cfg, batch, max_len, num_blocks,
                                         block_size, dtype)
        else:
            c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_groups,) + a.shape), c)

    return [one(s) for s in slots]


def _slot_prefill(x, p, cfg, slot, rope, old_cache, compute):
    """One layer over the full sequence, also producing its decode cache."""
    h = apply_norm(x, p["mixer_norm"], cfg)
    if slot["mixer"] == "attn":
        out, nc = attn.attention_prefill(
            h, p["mixer"], cfg, rope, old_cache,
            window=cfg.sliding_window, compute=compute)
    else:
        out, nc = ssm_mod.ssm_forward_with_cache(h, p["mixer"], cfg,
                                                 compute=compute)
    x = _residual(x, out, cfg)
    if slot["ffn"] != "none":
        h = apply_norm(x, p["ffn_norm"], cfg)
        if slot["ffn"] == "dense":
            h = apply_mlp(h, p["ffn"], cfg, compute)
        else:
            h, _ = moe_mod.apply_moe(h, p["ffn"], cfg, compute)
        x = _residual(x, h, cfg)
    return x, nc


def lm_prefill(params, cfg, tokens, cache, *, extra_embeds=None,
               compute=jnp.bfloat16):
    """Full-sequence prefill: returns (last-position logits, filled cache)."""
    slots = layer_slots(cfg)
    x = _embed(params, cfg, tokens, compute)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(compute), x], axis=1)
    rope = _rope_for(cfg, x.shape[1])

    def group_body(x, inp):
        gparams, gcache = inp
        x = constrain(x, "b..")
        new_gcache = []
        for i, slot in enumerate(slots):
            x, nc = _slot_prefill(x, gparams[i], cfg, slot, rope, gcache[i],
                                  compute)
            new_gcache.append(nc)
        return x, new_gcache

    x, new_cache = jax.lax.scan(_remat(group_body, cfg), x,
                                (params["layers"], cache))
    x = apply_norm(x, params["final_norm"], cfg)
    logits = _logits(x[:, -1:], params, cfg)
    return logits, new_cache


def lm_decode(params, cfg, token, cache, pos, *, block_tables=None,
              compute=jnp.bfloat16):
    """One decode step.  token: (B,1) int32; pos: scalar or (B,) int32
    absolute position(s) of the new token — per-row positions are the
    continuous-batching serve path.  ``block_tables`` (B, mb) routes paged
    cache leaves; one table serves every layer (all pools share physical
    block ids).  Returns (logits (B,1,V), new cache)."""
    slots = layer_slots(cfg)
    x = _embed(params, cfg, token, compute)

    def group_body(x, inp):
        gparams, gcache = inp
        x = constrain(x, "b..")
        new_gcache = []
        for i, slot in enumerate(slots):
            p = gparams[i]
            h = apply_norm(x, p["mixer_norm"], cfg)
            if slot["mixer"] == "attn":
                h, nc = attn.attention_decode(
                    h, p["mixer"], cfg, gcache[i], pos,
                    window=cfg.sliding_window, block_tables=block_tables,
                    compute=compute)
            else:
                h, nc = ssm_mod.ssm_decode(h, p["mixer"], cfg, gcache[i],
                                           compute=compute)
            new_gcache.append(nc)
            x = _residual(x, h, cfg)
            if slot["ffn"] != "none":
                h = apply_norm(x, p["ffn_norm"], cfg)
                if slot["ffn"] == "dense":
                    h = apply_mlp(h, p["ffn"], cfg, compute)
                else:
                    h, _ = moe_mod.apply_moe_dense(h, p["ffn"], cfg, compute)
                x = _residual(x, h, cfg)
        return x, new_gcache

    x, new_cache = jax.lax.scan(group_body, x, (params["layers"], cache))
    x = apply_norm(x, params["final_norm"], cfg)
    logits = _logits(x, params, cfg)
    return logits, new_cache


def lm_verify(params, cfg, tokens, cache, pos, *, block_tables=None,
              compute=jnp.bfloat16):
    """Speculative-verify forward: score S = k+1 consecutive positions of
    every row in ONE pass.  tokens: (B,S) int32 — ``tokens[:,0]`` is the
    pending token at ``pos`` and ``tokens[:,1:]`` the draft proposals;
    pos: (B,) absolute position of tokens[:,0].  Structurally `lm_decode`
    with an S-wide token axis: every position-wise op (embed, norms, MLP,
    dense-MoE, logits) batches over S, while the attention mixer loops the
    S queries through the exact single-token attend — which is what keeps
    each position's logits bitwise-equal to the sequential decode steps it
    replaces.  Paged attention-only archs: SSM mixers have no multi-token
    state-rollback path (the engine falls back to spec="off" for them).
    Returns (logits (B,S,V), new cache)."""
    slots = layer_slots(cfg)
    x = _embed(params, cfg, tokens, compute)

    def group_body(x, inp):
        gparams, gcache = inp
        x = constrain(x, "b..")
        new_gcache = []
        for i, slot in enumerate(slots):
            if slot["mixer"] != "attn":
                raise ValueError(
                    f"{cfg.name}: speculative verify needs every mixer to "
                    "be paged attention; SSM state rows advance one token "
                    "at a time and cannot roll back a rejected suffix")
            p = gparams[i]
            h = apply_norm(x, p["mixer_norm"], cfg)
            h, nc = attn.attention_verify(
                h, p["mixer"], cfg, gcache[i], pos,
                block_tables=block_tables, compute=compute)
            new_gcache.append(nc)
            x = _residual(x, h, cfg)
            if slot["ffn"] != "none":
                h = apply_norm(x, p["ffn_norm"], cfg)
                if slot["ffn"] == "dense":
                    h = apply_mlp(h, p["ffn"], cfg, compute)
                else:
                    h, _ = moe_mod.apply_moe_dense(h, p["ffn"], cfg, compute)
                x = _residual(x, h, cfg)
        return x, new_gcache

    x, new_cache = jax.lax.scan(group_body, x, (params["layers"], cache))
    x = apply_norm(x, params["final_norm"], cfg)
    logits = _logits(x, params, cfg)
    return logits, new_cache


def lm_prefill_chunk(params, cfg, tokens, cache, table_row, slot,
                     q_offset, *, compute=jnp.bfloat16):
    """One CHUNK of an admission prefill, into ONE batch row of the shared
    (paged) decode cache.  tokens: (1,C) int32; table_row: (mb,) int32 the
    admitted row's physical block ids; slot: scalar int32 batch row;
    q_offset: scalar int32 absolute position of tokens[:,0].  Only row
    `slot`'s state (its blocks / ring row / ssm row) is written — the
    other rows keep decoding bit-identically in between chunks.  Returns
    (last-position logits (1,V), new cache)."""
    slots = layer_slots(cfg)
    x = _embed(params, cfg, tokens, compute)

    def group_body(x, inp):
        gparams, gcache = inp
        x = constrain(x, "b..")
        new_gcache = []
        for i, slot_s in enumerate(slots):
            p = gparams[i]
            h = apply_norm(x, p["mixer_norm"], cfg)
            if slot_s["mixer"] == "attn":
                h, nc = attn.attention_prefill_chunk(
                    h, p["mixer"], cfg, gcache[i], table_row, slot,
                    q_offset, window=cfg.sliding_window, compute=compute)
            else:
                h, nc = ssm_mod.ssm_prefill_chunk_row(
                    h, p["mixer"], cfg, gcache[i], slot, compute=compute)
            new_gcache.append(nc)
            x = _residual(x, h, cfg)
            if slot_s["ffn"] != "none":
                h = apply_norm(x, p["ffn_norm"], cfg)
                if slot_s["ffn"] == "dense":
                    h = apply_mlp(h, p["ffn"], cfg, compute)
                else:
                    h, _ = moe_mod.apply_moe_dense(h, p["ffn"], cfg, compute)
                x = _residual(x, h, cfg)
        return x, new_gcache

    x, new_cache = jax.lax.scan(group_body, x, (params["layers"], cache))
    x = apply_norm(x, params["final_norm"], cfg)
    logits = _logits(x[:, -1:], params, cfg)
    return logits[:, 0], new_cache
