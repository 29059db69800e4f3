"""Public model API: one entry point per (arch family x mode).

``build_model(cfg)`` returns a :class:`ModelBundle` whose members are pure
functions suitable for jit / pjit / AOT lowering:

* ``init(key)``                      -> params
* ``loss(params, batch)``            -> (scalar, metrics)        [train]
* ``prefill(params, batch)``         -> (last logits, cache)     [prefill]
* ``decode(params, state)``          -> (logits, new state)      [decode]
* ``train_batch_specs(shape)``       -> ShapeDtypeStruct pytree
* ``decode_state_specs(shape)``      -> ShapeDtypeStruct pytree

The bundle is exactly what the pilot system's :class:`PayloadImage` compiles
when a payload is late-bound onto a slice.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import encdec as encdec_mod
from repro.models import transformer as tf


def _text_len(cfg: ArchConfig, seq_len: int) -> int:
    """VLM stubs spend part of the assigned seq budget on patch embeds."""
    if cfg.family == "vlm":
        return seq_len - cfg.frontend_tokens
    return seq_len


def _has_frontend(cfg: ArchConfig) -> bool:
    return cfg.family in ("vlm", "audio")


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig
    init: Callable[[Any], Any]
    loss: Callable[[Any, Any], Any]
    prefill: Callable[[Any, Any], Any]
    decode: Callable[[Any, Any], Any]
    # (params, state, tokens (1,C), table_row (mb,), slot, q_offset)
    # -> (logits (1,V), state) — one chunk of an admission prefill into one
    # row of a PAGED decode state; None for families without a chunked
    # path (enc-dec).
    prefill_chunk: Callable[..., Any] | None = None
    # (params, tokens (B,S), state) -> (logits (B,S,V), new state) — the
    # speculative-verify forward: score S = k+1 consecutive positions
    # (pending token + k draft proposals) of every row in one pass over
    # the PAGED cache.  Each position's logits are bitwise-equal to the
    # sequential decode steps the verify replaces.  None for enc-dec.
    verify: Callable[..., Any] | None = None

    # ---- shape specs (ShapeDtypeStruct stand-ins; no allocation) ----------

    def train_batch_specs(self, shape: ShapeSpec, compute=jnp.bfloat16):
        cfg = self.cfg
        B = shape.global_batch
        S = _text_len(cfg, shape.seq_len)
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if _has_frontend(cfg):
            specs["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_tokens, cfg.d_model), compute)
        return specs

    def prefill_batch_specs(self, shape: ShapeSpec, compute=jnp.bfloat16):
        return {k: v for k, v in self.train_batch_specs(shape, compute).items()
                if k != "targets"}

    def decode_state_specs(self, shape: ShapeSpec, dtype=jnp.bfloat16):
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        state = jax.eval_shape(
            functools.partial(init_decode_state, cfg, B, S, dtype=dtype))
        return state

    def param_specs(self):
        return jax.eval_shape(lambda: self.init(jax.random.key(0)))


def default_num_blocks(batch: int, max_len: int, block_size: int) -> int:
    """Pool size matching the dense cache's token capacity, plus the
    reserved scratch block (id 0, the garbage sink for free slots)."""
    return batch * (max_len // block_size) + 1


def init_decode_state(cfg: ArchConfig, batch: int, max_len: int, *,
                      dtype=jnp.bfloat16, kv: str = "dense",
                      num_blocks: int | None = None, block_size: int = 16,
                      mesh=None):
    """Concrete zero decode state (also used via eval_shape for specs).

    ``pos`` is a per-row (batch,) vector: every batch row decodes at its own
    absolute position, which is what lets the serving engine refill one slot
    mid-flight (continuous batching) instead of wave-stepping the whole
    block.  Rows that advance in lockstep simply carry equal entries.

    ``kv="paged"`` swaps the dense per-row KV slabs for shared block pools
    plus a per-row ``block_tables`` (batch, max_len // block_size) map; the
    table width times the block size equals ``max_len`` so the gathered
    logical view has the dense shapes (bitwise-equal attend math).

    ``mesh`` places the fresh state per the serve tensor-parallel rules
    (:func:`repro.runtime.sharding.serve_state_shardings`): KV pools shard
    on their head/latent dim over "model", block tables and scalars
    replicate.  Must stay None under ``eval_shape`` (specs carry no
    placement)."""
    if cfg.is_encdec:
        if kv == "paged":
            raise ValueError("paged KV is a decoder-LM path; "
                             f"{cfg.name} is enc-dec (use kv='dense')")
        cache = encdec_mod.init_encdec_cache(cfg, batch, max_len, dtype)
    elif kv == "paged":
        if max_len % block_size:
            raise ValueError(
                f"paged KV needs max_len % block_size == 0, got "
                f"{max_len} % {block_size}")
        nb = num_blocks or default_num_blocks(batch, max_len, block_size)
        cache = tf.init_cache_paged(cfg, batch, max_len, nb, block_size,
                                    dtype)
    else:
        cache = tf.init_cache(cfg, batch, max_len, dtype)
    state = {
        "cache": cache,
        "token": jnp.zeros((batch, 1), jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if kv == "paged":
        state["block_tables"] = jnp.zeros(
            (batch, max_len // block_size), jnp.int32)
    if mesh is not None:
        from repro.runtime.sharding import serve_state_shardings
        shardings = serve_state_shardings(state, mesh, cfg)
        state = jax.tree.map(jax.device_put, state, shardings)
    return state


def build_model(cfg: ArchConfig, compute=jnp.bfloat16) -> ModelBundle:
    if cfg.is_encdec:
        return _build_encdec(cfg, compute)
    return _build_lm(cfg, compute)


def _build_lm(cfg, compute):
    def init(key):
        return tf.init_lm_params(cfg, key)

    def loss(params, batch):
        return tf.lm_loss(params, cfg, batch["tokens"], batch["targets"],
                          extra_embeds=batch.get("frontend"), compute=compute)

    def prefill(params, batch):
        B = batch["tokens"].shape[0]
        S = batch["tokens"].shape[1] + (
            cfg.frontend_tokens if _has_frontend(cfg) else 0)
        cache = tf.init_cache(cfg, B, S, dtype=compute)
        return tf.lm_prefill(params, cfg, batch["tokens"], cache,
                             extra_embeds=batch.get("frontend"),
                             compute=compute)

    def decode(params, state):
        bt = state.get("block_tables")
        logits, cache = tf.lm_decode(params, cfg, state["token"],
                                     state["cache"], state["pos"],
                                     block_tables=bt, compute=compute)
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = {"cache": cache, "token": token, "pos": state["pos"] + 1}
        if bt is not None:
            out["block_tables"] = bt
        return logits, out

    def prefill_chunk(params, state, tokens, table_row, slot, q_offset):
        logits, cache = tf.lm_prefill_chunk(
            params, cfg, tokens, state["cache"], table_row, slot, q_offset,
            compute=compute)
        return logits, {**state, "cache": cache}

    def verify(params, tokens, state):
        logits, cache = tf.lm_verify(params, cfg, tokens, state["cache"],
                                     state["pos"],
                                     block_tables=state.get("block_tables"),
                                     compute=compute)
        return logits, {**state, "cache": cache}

    return ModelBundle(cfg, init, loss, prefill, decode,
                       prefill_chunk=prefill_chunk, verify=verify)


def _build_encdec(cfg, compute):
    def init(key):
        return encdec_mod.init_encdec_params(cfg, key)

    def loss(params, batch):
        return encdec_mod.encdec_loss(params, cfg, batch["frontend"],
                                      batch["tokens"], batch["targets"],
                                      compute=compute)

    def prefill(params, batch):
        B, S = batch["tokens"].shape
        cache = encdec_mod.init_encdec_cache(cfg, B, S, dtype=compute)
        return encdec_mod.encdec_prefill(params, cfg, batch["frontend"],
                                         batch["tokens"], cache,
                                         compute=compute)

    def decode(params, state):
        logits, cache = encdec_mod.encdec_decode(params, cfg, state["token"],
                                                 state["cache"], state["pos"],
                                                 compute=compute)
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return logits, {"cache": cache, "token": token,
                        "pos": state["pos"] + 1}

    return ModelBundle(cfg, init, loss, prefill, decode)
