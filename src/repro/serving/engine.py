"""Batched serving engine: continuous batching over a PAGED KV cache.

The engine owns a device-resident block pool per attention layer —
``(num_blocks, block_size, heads, dh)`` — plus a per-slot block table
``(slots, max_len // block_size)`` mapping logical position ``p`` of slot
``s`` to ``pool[table[s, p // bs], p % bs]``.  A host-side
:class:`~repro.serving.blockpool.BlockAllocator` (free list + refcounts)
hands out physical blocks at ADMISSION granularity: a request maps
exactly the blocks its prompt bucket + token budget can reach (not the
engine-wide ``max_len`` row a dense slab burns), and eviction returns
them all.  Allocating the whole row up front keeps the decode loop free
of host→device table maintenance — the block table is written once per
admission.  ``kv="dense"`` keeps the old (slots, max_len) slab as an
ablation — paged decode is bitwise-equal to it (same shapes, same masks,
same reduction order), which the CI smoke asserts.

On top of paging:

* **prefix reuse** — admission hashes the padded prompt per full block
  (chain hash, so a hit guarantees bit-identical KV); matching leading
  blocks are mapped into the slot's table copy-free with a refcount bump.
  One-shot admission still recomputes the whole prefill (reuse saves pool
  MEMORY); chunked admission additionally starts at the hit frontier and
  skips the shared blocks' compute.
  Shared blocks are copy-on-write safe by construction: only FULL blocks
  strictly below the write frontier are shared and nothing ever writes
  below the frontier, so the "copy" branch of COW is unreachable.
* **chunked prefill** (``prefill="chunked"``) — admission prefill is
  split into fixed-size chunks, at most ONE of which runs per engine
  tick, interleaved with the running slots' decode step: no decode step
  is ever delayed by more than one chunk (the stop-the-world admission
  of ``prefill="oneshot"`` is the ablation).  Mid-admission, the slot's
  device-side table row still points at the scratch block — the chunk
  executable carries the real row as an argument — so free-slot garbage
  writes cannot corrupt the half-prefilled request.

Per-slot ``pos`` invariants (unchanged from the dense engine):

* after admission into slot ``s`` with prompt bucket ``plen``,
  ``pos[s] == plen`` and logical rows ``0..plen-1`` hold the
  (left-padded) prompt KV;
* each decode step writes row ``s``'s KV at ``pos[s]`` and advances
  ``pos[s] += 1`` — rows never interact, so admitting a request
  mid-decode leaves every other slot's token stream bitwise identical
  to a solo run;
* a slot is evicted when ``pos[s]`` reaches ``max_len`` or its token
  budget is spent — both checked ON DEVICE; eviction returns every
  block the slot owned to the free list (refcount-decrement for shared
  prefix blocks);
* free slots keep stepping over garbage (cheaper than masking the
  batched matmuls); their paged writes land in the reserved scratch
  block 0, never in a live request's blocks.

One-transfer-per-step rule: the decode loop is device-resident; the
packed ``(2, slots)`` tokens/done array is the ONLY device→host transfer
per decode step (``d2h_transfers == steps``, asserted in tests).  Block-
table maintenance is host→device only.

In the pilot system this engine is a first-class *payload*: ``serve``
tasks late-bind it onto an already-held slice and drive it either from a
request trace in the startup spec or by leasing requests from a fleet
pool (core/images.py + core/wrapper.py + serving/dispatch.py); the serve
heartbeat telemetry now carries ``kv_memory_utilization`` and
``prefix_hit_rate`` so pilots report cache pressure upstream.  For the
fleet path the engine exposes per-request drain/export — ``cancel(rid)``
evicts a request wherever it lives and returns it for re-dispatch,
``drain_requests()`` exports everything — and ``warm_install()`` absorbs
the admission-install compile storm before a server takes leases.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.api import build_model, init_decode_state
from repro.models.attention import pool_rows
from repro.models.moe import dropless_rows
from repro.serving.blockpool import BlockAllocator, KVHandoff, PrefixCache


# --------------------------------------------------------------------------
# tensor-parallel helpers (no-ops when mesh is None)
# --------------------------------------------------------------------------

def _replicate(mesh, x):
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec()))


def _traced_under_mesh(fn, mesh):
    """Make ``fn`` trace under the serve activation-sharding context, so the
    model's serve-TP constraints (``constrain_replicated`` before every
    cross-shard contraction) bake into its jaxpr.  Prefill produces the
    admission token, which must match the single-device engine bitwise just
    like decode tokens — so prefill traces need the same treatment as the
    step functions.  Identity when there is no mesh."""
    if mesh is None or fn is None:
        return fn
    from repro.runtime.sharding import activation_sharding

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with activation_sharding(mesh, "serve"):
            return fn(*args, **kwargs)
    return wrapped


def _constrain_serve_state(mesh, state, cfg):
    """Pin the decode state's output shardings inside a jitted step: pools
    stay head-sharded, tables/scalars stay replicated.  Without this, GSPMD
    is free to re-partition the outputs between steps, which would make
    the engine's host-side install surgery reshard every tick."""
    from repro.runtime.sharding import serve_state_shardings
    shardings = serve_state_shardings(state, mesh, cfg)
    return jax.tree.map(jax.lax.with_sharding_constraint, state, shardings)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    submitted: float = dataclasses.field(default_factory=time.monotonic)
    # filled on completion
    tokens: list = dataclasses.field(default_factory=list)
    first_token_s: float | None = None
    done_s: float | None = None
    # disaggregated serving: a prefill-role engine fills this on export;
    # a decode-role engine resumes from it instead of a raw prompt
    handoff: KVHandoff | None = None


@dataclasses.dataclass
class SlotState:
    rid: int = -1                      # -1 == free
    active: bool = False               # decoding (False mid-admission)


@dataclasses.dataclass
class _PrefillJob:
    """A chunked admission in flight: slot is claimed, blocks are mapped,
    ``off`` tracks the next chunk's absolute start position."""
    si: int
    req: Request
    padded: np.ndarray                 # (plen,) int32 left-padded prompt
    plen: int
    off: int                           # == prefix-hit tokens at creation
    row: list                          # physical block ids (prefix + fresh)
    keys: list                         # full-block chain-hash keys
    nhit: int = 0                      # prefix-hit blocks (draft install)


def admit_length(prompt_len: int, max_len: int) -> int:
    """Round a prompt length up to its power-of-two bucket, rejecting
    prompts that cannot decode a single token inside the engine's KV
    budget.  Raises ValueError instead of silently cropping.

    The bucket is capped at ``max_len - 1``: prefill occupies ``plen``
    positions and decode starts writing KV at ``pos == plen``, so a bucket
    equal to ``max_len`` would leave zero decode room (the first decode
    write would clamp onto the last prompt position and corrupt it).
    """
    if prompt_len >= max_len:
        raise ValueError(
            f"prompt length {prompt_len} exceeds the admission cap "
            f"{max_len - 1} (= max_len {max_len} minus the >=1 KV row "
            f"decode needs); truncate the prompt to <= {max_len - 1} "
            f"tokens or build the engine with a larger max_len")
    b = 16
    while b < prompt_len:
        b *= 2
    return min(b, max_len - 1)


def admit_buckets(max_len: int) -> list[int]:
    """Every prompt bucket `admit_length` can produce for this ``max_len``
    (powers of two below the cap, plus the ``max_len - 1`` cap itself).
    `ExecutableRegistry.prefetch` stages a jitted prefill trace for each,
    so no first-request-of-a-bucket ever pays a retrace spike."""
    out = []
    b = 16
    while b < max_len - 1:
        out.append(b)
        b *= 2
    out.append(max_len - 1)
    return out


def prefill_chunk_shapes(max_len: int, block_size: int,
                         chunk: int) -> list[int]:
    """Every chunk length chunked admission can produce: chunk boundaries
    are aligned to absolute multiples of ``chunk``, and a prefix hit can
    start a job at any block boundary, so the set is {min(chunk - off %
    chunk, plen - off)} over all buckets and block-aligned offsets.  Small
    and static — warmable ahead of the first request."""
    shapes = set()
    for plen in admit_buckets(max_len):
        for off in range(0, plen, block_size):
            shapes.add(min(chunk - off % chunk, plen - off))
    return sorted(shapes)


# The layer scan writes every layer's new cache into a fresh stacked buffer
# (its ys), never into the one it reads (its xs).  A donated state would
# make XLA copy that whole buffer into the donated one at the end of each
# step, the size of every KV pool; left undonated, the scan's buffer is the
# step's output and the old state is freed when the engine drops it.  The
# peak is the same: two pools live during a step either way.  Only the
# (slots,) active and budget vectors are donated.
_STEP_DONATE = (2, 3)


def make_engine_step(bundle, max_len: int, mesh=None):
    """The engine's jitted decode step: decode + argmax + per-slot budget
    debit + done mask, all on device, returning one packed (2, slots) int32
    array.  Module-level so engines built over the SAME bundle/max_len (a
    serve image's factory) share one jit wrapper — which is what lets
    ``ExecutableRegistry.prefetch`` stage the XLA compile before the
    payload's first tick.  The same wrapper serves dense AND paged states
    (different pytree structures trace separately).

    ``mesh`` makes the step SPMD: the body traces under an
    ``activation_sharding`` context (model code then constrains activations
    and dispatches head-sharded Pallas kernels), output state shardings are
    pinned, and ``packed`` is constrained fully replicated so the engine's
    single ``device_get`` stays one transfer — it reads one local shard.

    The state is not donated (see `_STEP_DONATE`)."""
    def body(params, state, active, budget):
        logits, new_state = bundle.decode(params, state)       # argmax inside
        tok = new_state["token"][:, 0]
        budget = budget - active.astype(jnp.int32)
        done = active & ((budget <= 0) | (new_state["pos"] >= max_len))
        packed = jnp.stack([tok, done.astype(jnp.int32)])      # (2, slots)
        return packed, new_state, active & ~done, budget

    if mesh is None:
        return jax.jit(body, donate_argnums=_STEP_DONATE)

    from repro.runtime.sharding import activation_sharding

    def step(params, state, active, budget):
        with activation_sharding(mesh, "serve"):
            packed, new_state, active, budget = body(
                params, state, active, budget)
            new_state = _constrain_serve_state(mesh, new_state, bundle.cfg)
            packed = _replicate(mesh, packed)
            active = _replicate(mesh, active)
            budget = _replicate(mesh, budget)
        return packed, new_state, active, budget

    return jax.jit(step, donate_argnums=_STEP_DONATE)


def make_draft_step(bundle, k: int, max_len: int, mesh=None):
    """The draft half of a speculative step: ``k`` autoregressive draft
    decodes fused into one jitted ``lax.scan`` (one dispatch, zero
    device→host syncs).  The draft writes its KV into its OWN paged pools,
    addressed by the TARGET's block tables — same physical block ids, so
    admission/eviction bookkeeping covers both caches for free.  Returns
    ``(drafts (slots, k) int32, new draft cache)``."""
    def draft(params, cache, token, pos, block_tables):
        state = {"cache": cache, "token": token, "pos": pos,
                 "block_tables": block_tables}

        def body(st, _):
            # clamp the write position: a row whose speculative reach
            # crosses max_len keeps overwriting the last in-bounds
            # position — a block only this row can own (prefix sharing
            # never reaches the final position's block) — and drafts past
            # the end can never be accepted (acceptance clamps at
            # max_len - pos), so live KV is untouched either way
            _, nst = bundle.decode(
                params, {**st, "pos": jnp.minimum(st["pos"], max_len - 1)})
            nst = {**nst, "pos": st["pos"] + 1}
            return nst, nst["token"][:, 0]

        state, toks = jax.lax.scan(body, state, None, length=k)
        return jnp.transpose(toks), state["cache"]

    if mesh is None:
        return jax.jit(draft, donate_argnums=(1,))

    from repro.runtime.sharding import activation_sharding

    def draft_tp(params, cache, token, pos, block_tables):
        with activation_sharding(mesh, "serve"):
            toks, cache = draft(params, cache, token, pos, block_tables)
            cache = _constrain_serve_state(mesh, cache, bundle.cfg)
            # drafts feed verify device-side; replicated keeps the verify
            # trace free of a gather prologue
            toks = _replicate(mesh, toks)
        return toks, cache

    return jax.jit(draft_tp, donate_argnums=(1,))


def make_verify_step(bundle, max_len: int, k: int, mesh=None):
    """The verify half of a speculative step: ONE batched (k+1)-position
    target forward over [pending token, k drafts], then greedy acceptance
    (truncate at the first draft/target mismatch), budget debit and done
    mask — all on device.  The packed return is a single (k+3, slots)
    int32 array riding the engine's one-transfer-per-step contract:
    row 0 = accepted length ``a`` (0 for free slots), row 1 = done flags,
    rows 2..k+2 = the k+1 target-verified tokens (the host appends the
    first ``a`` of them).  Rejected suffixes need no device work to roll
    back: the host frontier simply does not advance over them, the next
    step's writes land at the committed frontier and overwrite, and
    per-query causal masks hide anything beyond ``pos``."""
    def step(params, state, active, budget, drafts):
        tokens = jnp.concatenate([state["token"], drafts], axis=1)
        logits, new_state = bundle.verify(params, tokens, state)
        preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B, k+1)
        # t_{s+1} is valid iff its input d_s matched the target's own
        # pick t_s at every position up to s: cumprod of the match mask
        match = (preds[:, :k] == drafts).astype(jnp.int32)
        a = 1 + jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        # clamp to the slot's remaining budget and max_len room (verify
        # probes up to k positions past both; the overshoot is garbage by
        # construction and must not be committed), zero for free slots
        a = jnp.minimum(a, jnp.minimum(budget, max_len - state["pos"]))
        a = jnp.maximum(a, 0) * active.astype(jnp.int32)
        budget = budget - a
        pos = state["pos"] + a
        done = active & ((budget <= 0) | (pos >= max_len))
        token = jnp.take_along_axis(
            preds, jnp.maximum(a - 1, 0)[:, None], axis=1)
        token = jnp.where(active[:, None], token, state["token"])
        new_state = {**new_state, "token": token, "pos": pos}
        packed = jnp.concatenate(
            [a[None], done.astype(jnp.int32)[None], preds.T], axis=0)
        return packed, new_state, active & ~done, budget

    if mesh is None:
        return jax.jit(step, donate_argnums=_STEP_DONATE)

    from repro.runtime.sharding import activation_sharding

    def step_tp(params, state, active, budget, drafts):
        with activation_sharding(mesh, "serve"):
            packed, new_state, active, budget = step(
                params, state, active, budget, drafts)
            new_state = _constrain_serve_state(mesh, new_state, bundle.cfg)
            packed = _replicate(mesh, packed)
            active = _replicate(mesh, active)
            budget = _replicate(mesh, budget)
        return packed, new_state, active, budget

    return jax.jit(step_tp, donate_argnums=_STEP_DONATE)


def spec_ineligible_reason(cfg, kv: str) -> str | None:
    """Why an arch cannot run draft-and-verify speculation (None == it
    can).  Mirrors the PR 3 dense fallback: instead of failing, the engine
    records the reason and serves non-speculatively."""
    if cfg.is_encdec:
        return "enc-dec archs have no decoder-only verify path"
    if cfg.is_attention_free or cfg.ssm is not None:
        return ("SSM state rows advance one token at a time and cannot "
                "roll back a rejected speculative suffix")
    if cfg.sliding_window is not None:
        return ("SWA rolling rings overwrite history in place and cannot "
                "roll back a rejected speculative suffix")
    if kv != "paged":
        return ("speculative rollback rides the paged block tables; "
                "kv='dense' has no frontier to truncate")
    return None


def handoff_ineligible_reason(cfg, kv: str) -> str | None:
    """Why an arch cannot serve in a disaggregated role (None == it can).
    The KV handoff moves PAGED BLOCKS between pools, so every per-token
    byte a decode step reads must live inside blocks — per-row state (SSM
    scan rows, SWA rolling rings) has no block id to ship."""
    if cfg.is_encdec:
        return "enc-dec archs do not run the decoder-only serve path"
    if cfg.is_attention_free or cfg.ssm is not None:
        return ("SSM state rows are per-slot, not per-block; they cannot "
                "ride a block-chain handoff")
    if cfg.sliding_window is not None:
        return ("SWA ring rows are per-slot, not per-block; they cannot "
                "ride a block-chain handoff")
    if kv != "paged":
        return "the handoff ships paged blocks; kv='dense' has none"
    return None


class ServeEngine:
    """Continuous-batching engine over a paged KV cache.

    * ``kv`` — "paged" (default for decoder LMs) or "dense" (the seed
      slab layout, kept as the benchmark ablation; forced for enc-dec).
    * ``prefill`` — "oneshot" (whole-bucket prefill at admission) or
      "chunked" (``prefill_chunk``-token chunks interleaved with decode).
    * ``num_blocks`` — pool size; default matches the dense slab's token
      capacity (benchmarks shrink it to measure effective capacity).
    * ``prefix_sharing`` — hash-keyed prompt-prefix block reuse; enabled
      automatically only for architectures whose per-token state lives
      entirely in paged blocks (no SWA ring rows, no SSM state rows).
    * ``admission="wave"`` restores wave-scheduled refills (baseline).

    ``bundle``/``step_fn``/``prefill_fn``/``chunk_fn`` let a serve image's
    factory share one model bundle and its jitted wrappers across engine
    instances (jit caches are per wrapper, so sharing the wrapper is what
    makes a prefetched compile reusable)."""

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 admission: str = "continuous", kv: str | None = None,
                 block_size: int = 16, num_blocks: int | None = None,
                 prefill: str = "oneshot", prefill_chunk: int = 32,
                 prefix_sharing: bool = True, bundle=None, step_fn=None,
                 prefill_fn=None, chunk_fn=None,
                 spec: str = "off", spec_k: int = 4, draft_cfg=None,
                 draft_params=None, draft_bundle=None, draft_fn=None,
                 verify_fn=None, draft_prefill_fn=None, mesh=None,
                 role: str = "unified"):
        assert admission in ("continuous", "wave"), admission
        assert prefill in ("oneshot", "chunked"), prefill
        assert spec in ("off", "draft"), spec
        assert role in ("unified", "prefill", "decode"), role
        self.role = role
        # tensor-parallel serving: the whole engine state lives sharded on
        # `mesh` (params by the serve TP rules, KV pools on their head dim,
        # everything else replicated) and the jitted steps run SPMD.  A
        # 1-device mesh degrades to the single-device engine bit-for-bit.
        self.mesh = mesh
        self.mesh_devices = int(mesh.devices.size) if mesh is not None else 1
        # an arch only pages if some attention layer's per-token state can
        # live in blocks: all-SWA models are pure rolling rings and
        # attention-free models pure SSM state — a pool there would be
        # phantom memory (bookkeeping, telemetry and admission gating over
        # bytes that don't exist), so they fall back to the dense layout
        pages = (not cfg.is_encdec and not cfg.is_attention_free
                 and (cfg.mla is not None or cfg.sliding_window is None))
        if kv is None:
            kv = "paged" if pages else "dense"
        assert kv in ("paged", "dense"), kv
        if kv == "paged" and not pages:
            kv = "dense"
        if role != "unified":
            reason = handoff_ineligible_reason(cfg, kv)
            if reason is not None:
                raise ValueError(
                    f"role={role!r} needs the KV block handoff: {reason}")
        self.cfg = cfg
        if mesh is not None:
            from repro.runtime.sharding import serve_param_shardings
            params = jax.tree.map(
                jax.device_put, params, serve_param_shardings(params, mesh))
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.admission = admission
        self.kv = kv
        self.block_size = block_size
        self.bundle = bundle or build_model(cfg)
        # chunked admission works on both layouts (dense rings append like
        # a T == max_len rolling window) EXCEPT dense MLA, whose chunk
        # path only speaks the paged latent pools
        self.prefill_mode = (
            prefill if (self.bundle.prefill_chunk is not None
                        and (kv == "paged" or cfg.mla is None))
            else "oneshot")
        self.prefill_chunk = prefill_chunk

        if kv == "paged":
            assert prefill_chunk % block_size == 0, (prefill_chunk,
                                                     block_size)
            nb = num_blocks or (slots * (max_len // block_size) + 1)
            self._num_blocks = nb
            self.allocator = BlockAllocator(nb, block_size)
            # prefix reuse needs ALL per-token state inside paged blocks:
            # SWA ring rows and SSM state rows are per-slot and cannot be
            # remapped by block id, so those archs admit without sharing
            prefix_ok = (prefix_sharing and cfg.sliding_window is None
                         and cfg.ssm is None)
            self.prefix = PrefixCache(self.allocator) if prefix_ok else None
            self.state = init_decode_state(
                cfg, slots, max_len, kv="paged", num_blocks=nb,
                block_size=block_size, mesh=mesh)
            self.max_blocks_per_slot = max_len // block_size
        else:
            self.allocator = None
            self.prefix = None
            self.state = init_decode_state(cfg, slots, max_len, mesh=mesh)
            self.max_blocks_per_slot = 0
            self._num_blocks = 0
        self.budget = jnp.zeros((slots,), jnp.int32)          # device-side
        self.active = jnp.zeros((slots,), bool)               # device-side
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.runtime.sharding import serve_state_shardings
            rep = NamedSharding(mesh, PartitionSpec())
            self.budget = jax.device_put(self.budget, rep)
            self.active = jax.device_put(self.active, rep)
            # the target shardings the step functions pin; host-side
            # install surgery is repaired against this (``_ensure_sharded``)
            self._state_shardings = serve_state_shardings(
                self.state, mesh, cfg)
            self._rep_sharding = rep
        else:
            self._state_shardings = None
            self._rep_sharding = None
        self.slot_meta = [SlotState() for _ in range(slots)]
        self.queue: deque[Request] = deque()
        self._jobs: deque[_PrefillJob] = deque()
        self.done: dict[int, Request] = {}
        self._live: dict[int, Request] = {}
        # host mirrors (paged bookkeeping + cache-pressure stats)
        self._host_pos = [0] * slots
        self._slot_blocks: list[list[int]] = [[] for _ in range(slots)]
        self.steps = 0
        self.idle_slot_steps = 0       # slots with no request during a step
        self.d2h_transfers = 0         # must equal `steps` (one per step)
        self.prefill_chunks = 0
        self.blocked_admissions = 0    # admissions deferred on pool pressure
        self.prefills_exported = 0     # role="prefill": handoffs produced
        self.handoffs_imported = 0     # role="decode": handoffs resumed
        self.prompt_tokens_total = 0
        self.prefix_hit_tokens = 0
        self._kv_util_sum = 0.0
        self.kv_peak_live_tokens = 0
        # speculative-decode accounting (all zero when spec == "off")
        self.spec_drafted = 0          # draft proposals scored by verify
        self.spec_accepted = 0         # of those, committed to requests
        self.tokens_emitted = 0        # total committed tokens (all modes)
        # dropless MoE dispatch of the one-shot admission prefills, counted
        # from static shapes (no sync): token-expert pairs routed, and the
        # rows the grouped matmul ran, tile padding included
        self._moe_layers = len(cfg.moe_layer_indices())
        self.moe_rows = 0
        self.moe_rows_run = 0

        # one compiled decode step for the whole engine lifetime; budget
        # and active are donated every step, the decode state is not
        # (``_STEP_DONATE``).
        # A prefill-role engine never decodes (its slots turn over at the
        # handoff export) and a decode-role engine never prefills (its
        # admissions scatter imported blocks), so each drops the other
        # half's executables — the warm-time saving bench_bind measures.
        self._step_fn = (None if role == "prefill"
                         else step_fn or make_engine_step(self.bundle,
                                                          max_len, mesh=mesh))
        # one jitted prefill wrapper; jax re-traces per prompt bucket shape
        self._prefill = (None if role == "decode"
                         else prefill_fn or jax.jit(
                             _traced_under_mesh(self.bundle.prefill, mesh)))
        self._chunk_fn = (
            None if role == "decode"
            else chunk_fn or (
                jax.jit(_traced_under_mesh(self.bundle.prefill_chunk, mesh),
                        donate_argnums=1)
                if self.bundle.prefill_chunk is not None else None))
        if role == "decode":
            self.prefill_mode = "oneshot"    # no chunk path to interleave

        # ---- speculative decoding: draft-and-verify multi-token steps ----
        # the draft model is itself a late-binding decision: a serve image
        # names it in its payload spec and the engine falls back (recorded,
        # not fatal) wherever the arch cannot roll back a rejected suffix
        self.spec = "off"
        self.spec_k = int(spec_k)
        self.spec_fallback_reason = None
        if spec == "draft" and role != "unified":
            # the draft's shadow pools do not ride the handoff, so a
            # resumed request would draft over garbage KV; record the
            # fallback instead of failing, like every other spec gate
            self.spec_fallback_reason = (
                f"role={role}: draft KV does not ride the block handoff")
            spec = "off"
        if spec == "draft":
            reason = spec_ineligible_reason(cfg, self.kv)
            if reason is None and draft_cfg is not None:
                dr = spec_ineligible_reason(draft_cfg, "paged")
                if dr is not None:
                    reason = f"draft arch: {dr}"
                elif draft_cfg.vocab_size != cfg.vocab_size:
                    reason = ("draft vocab differs from target "
                              f"({draft_cfg.vocab_size} vs "
                              f"{cfg.vocab_size}); proposals would not be "
                              "target token ids")
            if reason is not None:
                self.spec_fallback_reason = reason
            else:
                self.spec = "draft"
        if self.spec == "draft":
            self.draft_cfg = draft_cfg or cfg
            # draft_cfg None == self-draft: the target proposes for itself
            # (the upper-bound ablation; every proposal is accepted)
            self.draft_bundle = draft_bundle or (
                self.bundle if draft_cfg is None
                else build_model(self.draft_cfg))
            if draft_params is not None:
                self.draft_params = draft_params
            elif draft_cfg is None:
                self.draft_params = params
            else:
                # fixed seed: every engine in a fleet reconstructs bitwise-
                # identical draft weights, so a requeued request replays the
                # same tokens on whichever server picks it up
                self.draft_params = self.draft_bundle.init(jax.random.key(0))
            if mesh is not None:
                from repro.runtime.sharding import serve_param_shardings
                self.draft_params = jax.tree.map(
                    jax.device_put, self.draft_params,
                    serve_param_shardings(self.draft_params, mesh))
            # the draft's paged pools shadow the target's: same num_blocks,
            # same block_size, addressed through the SAME block-table ids —
            # admission/eviction bookkeeping covers both caches at once
            self._draft_cache = init_decode_state(
                self.draft_cfg, slots, max_len, kv="paged",
                num_blocks=self._num_blocks,
                block_size=block_size, mesh=mesh)["cache"]
            self._draft_fn = draft_fn or make_draft_step(
                self.draft_bundle, self.spec_k, max_len, mesh=mesh)
            self._verify_fn = verify_fn or make_verify_step(
                self.bundle, max_len, self.spec_k, mesh=mesh)
            if mesh is not None:
                from repro.runtime.sharding import serve_state_shardings
                self._draft_shardings = serve_state_shardings(
                    self._draft_cache, mesh, self.draft_cfg)
            else:
                self._draft_shardings = None
            self._draft_prefill = draft_prefill_fn or jax.jit(
                _traced_under_mesh(self.draft_bundle.prefill, mesh))

    # ------------------------------------------------------------------

    @property
    def kv_capacity_tokens(self) -> int:
        """Total KV token capacity the engine's cache memory can hold."""
        if self.kv == "paged":
            return self.allocator.capacity_tokens
        return self.slots * self.max_len

    def submit(self, req: Request):
        """Admit a request.  A prompt that cannot fit the engine's KV
        budget (prompt + at least one generated token within ``max_len``,
        and — paged — a worst-case block reach within the pool) is
        rejected here, explicitly — never silently cropped or deferred
        forever."""
        if req.rid == -1:
            raise ValueError("request id -1 is reserved (the engine's "
                             "free-slot sentinel)")
        if req.handoff is not None:
            if self.role != "decode":
                raise ValueError(
                    f"role={self.role!r} engine cannot import a KV handoff "
                    "(only role='decode' resumes from one)")
            req.handoff.validate_against(self.kv_fingerprint())
            plen = req.handoff.plen
            if plen >= self.max_len:
                raise ValueError(
                    f"handoff bucket {plen} leaves no decode room inside "
                    f"max_len {self.max_len}")
            end_max = min(plen + req.max_new_tokens, self.max_len)
            need = -(-end_max // self.block_size)
            if need > self.allocator.capacity_blocks:
                raise ValueError(
                    f"handoff needs {need} KV blocks (bucket {plen} + "
                    f"budget {req.max_new_tokens}) but the pool holds "
                    f"{self.allocator.capacity_blocks}")
            self.queue.append(req)
            return
        if self.role == "decode":
            raise ValueError(
                "role='decode' engine only accepts handoff requests; "
                "route raw prompts to the prefill pool")
        plen = admit_length(len(req.prompt), self.max_len)
        if self.kv == "paged":
            # a prefill-role engine maps only the prompt's blocks — its
            # slots turn over at the export, so the decode budget's reach
            # is the DECODE pool's problem
            end_max = (plen if self.role == "prefill"
                       else min(plen + req.max_new_tokens, self.max_len))
            need = -(-end_max // self.block_size)
            if need > self.allocator.capacity_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks (prompt bucket {plen} "
                    f"+ budget {req.max_new_tokens}) but the pool holds "
                    f"{self.allocator.capacity_blocks}; admission could "
                    f"never succeed — shrink the request or grow "
                    f"num_blocks")
        self.queue.append(req)

    def _bucket(self, n: int) -> int:
        return admit_length(n, self.max_len)

    # ------------------------------------------------------------------
    # slot-granular admission
    # ------------------------------------------------------------------

    def _admit(self):
        """Fill free slots from the queue.  Continuous mode refills any free
        slot immediately; wave mode (baseline) only refills once ALL slots
        have drained.  Paged admission can defer on pool pressure (the
        request stays queued; `blocked_admissions` counts the stall)."""
        free = [i for i, m in enumerate(self.slot_meta) if m.rid == -1]
        if not free or not self.queue:
            return
        if self.admission == "wave" and len(free) < self.slots:
            return
        for si in free:
            if not self.queue:
                break
            if not self._admit_into(si, self.queue[0]):
                break                              # pool pressure: retry later
            self.queue.popleft()

    def _admit_into(self, si: int, req: Request) -> bool:
        """Begin admission of one request into batch row `si` while the
        other slots' decode state stays untouched.  Returns False when the
        paged pool cannot hold the request yet."""
        if req.handoff is not None:
            return self._admit_handoff_into(si, req)
        plen = self._bucket(len(req.prompt))
        bs = self.block_size
        padded = np.zeros((plen,), np.int32)
        padded[-len(req.prompt):] = req.prompt                # left-pad
        row, keys, hit, shareable = [], [], [], 0
        if self.kv == "paged":
            end_max = (plen if self.role == "prefill"
                       else min(plen + req.max_new_tokens, self.max_len))
            total_blocks = -(-end_max // bs)
            n_full = plen // bs
            # cap sharing below the last prompt position so admission
            # always has >= 1 chunk/prefill position to produce logits
            shareable = min(n_full, (plen - 1) // bs)
            keys = (PrefixCache.block_keys(padded, bs, n_full)
                    if self.prefix is not None else [])
            hit = self.prefix.match(keys[:shareable]) if self.prefix else []
            need = total_blocks - len(hit)
            if self.allocator.available_blocks < need:
                if self.prefix is not None:
                    self.prefix.evict_unreferenced(
                        need - self.allocator.available_blocks)
                if self.allocator.available_blocks < need:
                    for bid in hit:                # undo the match refs
                        self.allocator.free(bid)
                    self.blocked_admissions += 1
                    return False
            # map the request's WHOLE reach (prompt bucket + budget,
            # capped at max_len) now: the block table is then written once
            # per admission and the decode loop never touches it
            row = hit + [self.allocator.alloc() for _ in range(need)]
            self._slot_blocks[si] = list(row)
            self.prefix_hit_tokens += len(hit) * bs
        nhit = len(hit)
        self.prompt_tokens_total += plen
        self.slot_meta[si].rid = req.rid
        self._live[req.rid] = req

        if self.prefill_mode == "chunked":
            self._zero_ssm_rows(si)
            self._jobs.append(_PrefillJob(
                si=si, req=req, padded=padded, plen=plen,
                off=nhit * bs, row=row, keys=keys, nhit=nhit))
            return True

        with TraceAnnotation("repro.engine.admit", rid=req.rid):
            logits, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(padded[None])})
            if self._moe_layers:
                self.moe_rows += self._moe_layers * plen * self.cfg.moe.top_k
                self.moe_rows_run += (self._moe_layers
                                      * dropless_rows(plen, self.cfg)[0])
            nxt = int(jnp.argmax(logits[0, -1]))              # admission-time
            if self.kv == "paged":
                self.state = _install_slot_paged(
                    self.state, cache, si, plen, nxt, row, nhit, bs)
                self._publish_prefix(keys, row, nhit, shareable)
                self._install_draft(padded, row, nhit)
            else:
                self.state = _install_slot(self.state, cache, si, plen, nxt)
            if self.role == "prefill":
                self._finish_prefill_export(si, req, plen, nxt, padded, keys)
            else:
                self._finish_admission(si, req, plen, nxt)
        return True

    def _finish_admission(self, si: int, req: Request, plen: int, nxt: int):
        m = self.slot_meta[si]
        m.rid = req.rid
        m.active = True
        self.active = self.active.at[si].set(True)
        self.budget = self.budget.at[si].set(req.max_new_tokens)
        self._host_pos[si] = plen
        req.tokens.append(nxt)
        req.first_token_s = time.monotonic() - req.submitted
        self._live[req.rid] = req

    # ------------------------------------------------------------------
    # disaggregated serving: KV block export (prefill) / import (decode)
    # ------------------------------------------------------------------

    def kv_fingerprint(self) -> tuple:
        """Pool-layout identity a handoff must match: block size plus each
        layer's paged keys with the per-block shapes of their handoff
        buffers and their dtypes.  Two engines agree iff a block gathered
        from one scatters into the other unchanged — same arch family,
        head layout and KV dtype."""
        assert self.kv == "paged", "fingerprint is a paged-pool property"
        layers = tuple(
            tuple(sorted((k, v.shape[:1] + v.shape[2:3]
                          + _host_row_shape(k, v, self.cfg), str(v.dtype))
                         for k, v in leaf.items()
                         if k in self._PAGED_KEYS))
            for leaf in self.state["cache"])
        return (self.block_size, layers)

    def _finish_prefill_export(self, si: int, req: Request, plen: int,
                               nxt: int, padded: np.ndarray, keys: list):
        """Prefill-role completion: gather the slot's prompt block chain
        into contiguous host buffers, attach the chain-hash keys and the
        admission token, and finish the request — the slot and its blocks
        turn over immediately, which is what lets a prefill pool drain
        prompts at prefill service rate instead of holding slots for the
        whole decode."""
        bs = self.block_size
        n_pb = -(-plen // bs)
        n_full = plen // bs
        if not keys:
            # prefix sharing may be off here, but the DECODE pool still
            # wants the keys for republish — they only depend on the
            # padded tokens, not on this engine's cache
            keys = PrefixCache.block_keys(padded, bs, n_full)
        row = self._slot_blocks[si]
        bufs = _gather_blocks(self.state["cache"], row[:n_pb],
                              self._PAGED_KEYS, self.cfg)
        req.handoff = KVHandoff(
            rid=req.rid, prompt=np.asarray(req.prompt, np.int32),
            plen=plen, first_token=nxt, max_new_tokens=req.max_new_tokens,
            block_hashes=tuple(keys), fingerprint=self.kv_fingerprint(),
            blocks=bufs)
        now = time.monotonic()
        req.tokens.append(nxt)
        req.first_token_s = now - req.submitted
        req.done_s = now - req.submitted
        self.prefills_exported += 1
        self._live.pop(req.rid, None)
        self.done[req.rid] = req
        self._evict_slot(si)

    def _admit_handoff_into(self, si: int, req: Request) -> bool:
        """Decode-role admission: scatter an imported block chain into
        this pool and resume at the first generated token.  The installed
        slot state (``pos = plen``, ``token = first_token``, prompt KV in
        rows ``0..plen-1``) is EXACTLY what `_finish_admission` leaves
        behind on a unified engine, so the greedy stream continues
        bitwise identically.  Prefix-hit blocks are skipped in the
        scatter and fresh full blocks are republished under the handoff's
        own keys — sharing crosses the pool boundary."""
        h = req.handoff
        bs = self.block_size
        plen = h.plen
        end_max = min(plen + req.max_new_tokens, self.max_len)
        total_blocks = -(-end_max // bs)
        n_pb = -(-plen // bs)
        n_full = plen // bs
        shareable = min(n_full, (plen - 1) // bs)
        keys = list(h.block_hashes)
        hit = self.prefix.match(keys[:shareable]) if self.prefix else []
        need = total_blocks - len(hit)
        if self.allocator.available_blocks < need:
            if self.prefix is not None:
                self.prefix.evict_unreferenced(
                    need - self.allocator.available_blocks)
            if self.allocator.available_blocks < need:
                for bid in hit:                    # undo the match refs
                    self.allocator.free(bid)
                self.blocked_admissions += 1
                return False
        row = hit + [self.allocator.alloc() for _ in range(need)]
        self._slot_blocks[si] = list(row)
        nhit = len(hit)
        self.prefix_hit_tokens += nhit * bs
        self.prompt_tokens_total += plen
        self.slot_meta[si].rid = req.rid
        self.state = _import_blocks_paged(
            self.state, h.blocks, si, plen, h.first_token, row, nhit, bs)
        self._publish_prefix(keys, row, nhit, shareable)
        self.handoffs_imported += 1
        m = self.slot_meta[si]
        m.active = True
        self.active = self.active.at[si].set(True)
        self.budget = self.budget.at[si].set(req.max_new_tokens)
        self._host_pos[si] = plen
        if not req.tokens:
            # the stream already starts with prefill's admission token;
            # a replayed import (requeue-from-handoff) re-appends it on
            # the fresh Request the dispatcher rebuilt
            req.tokens.append(h.first_token)
        req.first_token_s = time.monotonic() - req.submitted
        self._live[req.rid] = req
        return True

    def _dummy_handoff(self, plen: int) -> KVHandoff:
        """A zero-KV handoff shaped exactly like a real one for bucket
        ``plen`` — `warm_install` feeds these through the import scatter
        so a decode server absorbs its compile storm before taking
        leases."""
        bs = self.block_size
        n_pb = -(-plen // bs)
        n_full = plen // bs
        prompt = (np.arange(max(plen - 1, 1)) % self.cfg.vocab_size).astype(
            np.int32)
        padded = np.zeros((plen,), np.int32)
        padded[-len(prompt):] = prompt
        blocks = [
            {k: np.zeros(v.shape[:1] + (n_pb, v.shape[2])
                         + _host_row_shape(k, v, self.cfg), v.dtype)
             for k, v in leaf.items() if k in self._PAGED_KEYS}
            for leaf in self.state["cache"]]
        return KVHandoff(
            rid=-2, prompt=prompt, plen=plen, first_token=0,
            max_new_tokens=1,
            block_hashes=tuple(PrefixCache.block_keys(padded, bs, n_full)),
            fingerprint=self.kv_fingerprint(), blocks=blocks)

    def _publish_prefix(self, keys, row, nhit: int, shareable: int):
        """Register freshly-filled full blocks, capped at the MATCHABLE
        range: the block holding the last prompt position can never be
        returned by `match` (admission must keep >= 1 position to compute
        logits), so publishing it would only pin pool capacity."""
        if self.prefix is None:
            return
        for j in range(nhit, shareable):
            self.prefix.publish(keys[j], row[j])

    def _zero_ssm_rows(self, si: int):
        """Chunked prefill scans SSM layers from the row's cached state, so
        a new request must start that row from zeros (paged/ring attention
        rows need no reset: their stale entries are masked or overwritten)."""
        if self.cfg.ssm is None:
            return
        new_cache = []
        for leaf in self.state["cache"]:
            if "conv" in leaf:
                leaf = {k: v.at[:, si].set(jnp.zeros_like(v[:, si]))
                        for k, v in leaf.items()}
            new_cache.append(leaf)
        self.state = {**self.state, "cache": new_cache}

    def _install_draft(self, padded, row, nhit: int):
        """Prompt-prefill the DRAFT model for a freshly admitted request
        and scatter its KV into the draft pools at the same physical block
        ids the target admission mapped.  Prefix-hit blocks are skipped:
        draft prefill is deterministic, so the admission that published a
        shared block already left bit-identical draft KV in the shadow
        pool — prefix reuse covers both caches for free."""
        if self.spec != "draft":
            return
        _, dcache = self._draft_prefill(
            self.draft_params, {"tokens": jnp.asarray(padded[None])})
        self._draft_cache = _install_draft_paged(
            self._draft_cache, dcache, row, nhit, self.block_size)

    # ------------------------------------------------------------------
    # chunked prefill: at most ONE chunk per engine tick
    # ------------------------------------------------------------------

    def _prefill_tick(self):
        if not self._jobs:
            return
        job = self._jobs[0]
        # chunk boundaries are aligned to absolute multiples of the chunk
        # size, so the set of chunk shapes stays closed under prefix-hit
        # offsets (see `prefill_chunk_shapes`) — no mid-serve retraces
        C = min(self.prefill_chunk - job.off % self.prefill_chunk,
                job.plen - job.off)
        toks = jnp.asarray(job.padded[None, job.off:job.off + C])
        # dense chunked admission (all-SWA / SSM archs) has no blocks: the
        # table-row arg is a 1-wide dummy no cache leaf ever indexes
        row_arr = np.zeros((max(self.max_blocks_per_slot, 1),), np.int32)
        row_arr[:len(job.row)] = job.row
        logits, self.state = self._chunk_fn(
            self.params, self.state, toks, jnp.asarray(row_arr),
            jnp.int32(job.si), jnp.int32(job.off))
        self.prefill_chunks += 1
        job.off += C
        if job.off < job.plen:
            return
        # last chunk landed: install the block-table row on device and
        # flip the slot to decoding (unified) or export the handoff and
        # turn the slot over (prefill role)
        nxt = int(jnp.argmax(logits[0]))
        if self.kv == "paged":
            self.state["block_tables"] = (
                self.state["block_tables"].at[job.si].set(
                    jnp.asarray(row_arr)))
        self.state["token"] = self.state["token"].at[job.si, 0].set(nxt)
        self.state["pos"] = self.state["pos"].at[job.si].set(job.plen)
        # the DRAFT prompt KV lands in one shot on the final chunk's tick:
        # the draft is orders of magnitude smaller than the target, so its
        # whole-bucket prefill costs less than one more target chunk would
        self._install_draft(job.padded, job.row, job.nhit)
        self._publish_prefix(
            job.keys, job.row, 0,
            min(job.plen // self.block_size,
                (job.plen - 1) // self.block_size))
        if self.role == "prefill":
            self._finish_prefill_export(job.si, job.req, job.plen, nxt,
                                        job.padded, job.keys)
        else:
            self._finish_admission(job.si, job.req, job.plen, nxt)
        self._jobs.popleft()

    # ------------------------------------------------------------------

    _PAGED_KEYS = ("kp", "vp", "ckvp", "kropep")

    def _guard_rows(self):
        """Snapshot the PER-ROW (non-paged) cache leaves — SSM state rows,
        SWA ring rows — of every mid-admission slot.  The scratch block
        only protects paged pools from free-slot garbage writes; the
        batched decode step advances per-row state unconditionally, which
        would corrupt a half-prefilled request between chunks.  Restored
        right after the step (`_restore_rows`)."""
        sis = sorted({job.si for job in self._jobs})
        if not sis:
            return None
        idx = jnp.asarray(sis)
        snap = [(li, k, v[:, idx])
                for li, leaf in enumerate(self.state["cache"])
                for k, v in leaf.items() if k not in self._PAGED_KEYS]
        return (idx, snap) if snap else None

    def _restore_rows(self, guard):
        idx, snap = guard
        cache = [dict(leaf) for leaf in self.state["cache"]]
        for li, k, v in snap:
            cache[li][k] = cache[li][k].at[:, idx].set(v)
        self.state = {**self.state, "cache": cache}

    def _evict_slot(self, si: int):
        # Frontier truncation doubles as the speculative rollback path: a
        # cancel or eviction can land MID-VERIFY, with draft/verify KV
        # written up to spec_k positions past the committed frontier (in
        # BOTH the target and the shadow draft pools).  Speculation never
        # allocates — admission maps the request's whole reach — so every
        # frontier extension lives in blocks this row already owns; freeing
        # `_slot_blocks` releases all of them and zeroing the device table
        # row makes the stale entries unreachable.  Refcounts therefore
        # balance exactly one free per admission-time alloc/share, with no
        # speculative remainder to leak or double-free (the cancel-mid-
        # verify churn test asserts the allocator returns to prefix-only).
        m = self.slot_meta[si]
        if self.kv == "paged":
            for bid in self._slot_blocks[si]:
                self.allocator.free(bid)
            self._slot_blocks[si] = []
            self.state["block_tables"] = (
                self.state["block_tables"].at[si].set(0))
        m.rid = -1
        m.active = False
        self._host_pos[si] = 0

    # ------------------------------------------------------------------
    # per-request drain/export: the fleet dispatcher's re-dispatch hooks
    # ------------------------------------------------------------------

    def cancel(self, rid: int) -> Request | None:
        """Remove request ``rid`` wherever it lives — the submit queue, a
        mid-admission chunked-prefill job, or a live decode slot — and
        return it for re-dispatch (tokens produced so far intact).  Evicting
        a slot returns every KV block it owned; the freed slot refills on
        the next tick.  Returns None when the engine does not hold ``rid``
        (already completed or never admitted here)."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                del self.queue[i]
                return r
        # mid-admission jobs claim a slot + blocks before they decode, and
        # must be checked BEFORE slot_meta so the job entry dies with them
        for j, job in enumerate(self._jobs):
            if job.req.rid == rid:
                del self._jobs[j]
                self._live.pop(rid, None)
                self._evict_slot(job.si)
                return job.req
        for si, m in enumerate(self.slot_meta):
            if m.rid == rid:
                req = self._live.pop(rid, None)
                self.active = self.active.at[si].set(False)
                self._evict_slot(si)
                return req
        return None

    def drain_requests(self) -> list[Request]:
        """Evict EVERY queued / mid-admission / decoding request and return
        them for re-dispatch on another engine (replay-from-prompt).  Used
        when a serving payload gives its remaining work back instead of
        letting the leases expire."""
        rids = dict.fromkeys(
            [r.rid for r in self.queue]
            + [j.req.rid for j in self._jobs]
            + [m.rid for m in self.slot_meta if m.rid != -1])
        out = []
        for rid in rids:
            req = self.cancel(rid)
            if req is not None:
                out.append(req)
        return out

    def _ensure_sharded(self):
        """Repair sharding drift before a mesh step: the eager host-side
        install/evict surgery (`.at[].set`, block scatters) can hand back
        leaves whose placement no longer matches the step's pinned
        shardings, which would force GSPMD to reshard (or jit to re-trace)
        every tick.  The `.sharding` comparison is pure host metadata —
        leaves already in place cost nothing."""
        if self.mesh is None:
            return

        def fix(x, s):
            return x if getattr(x, "sharding", None) == s else \
                jax.device_put(x, s)

        self.state = jax.tree.map(fix, self.state, self._state_shardings)
        rep = self._rep_sharding
        self.active = fix(self.active, rep)
        self.budget = fix(self.budget, rep)
        if self.spec == "draft":
            self._draft_cache = jax.tree.map(
                fix, self._draft_cache, self._draft_shardings)

    def step(self) -> int:
        """One engine iteration: admit into free slots, advance at most one
        prefill chunk, then one batched decode step.  Returns the number of
        live slots decoded (0 when no slot is decoding — an idle or
        admission-only tick is not a decode step)."""
        self._admit()
        self._prefill_tick()
        actives = [si for si, m in enumerate(self.slot_meta) if m.active]
        if not actives:
            return 0
        self._ensure_sharded()
        guard = self._guard_rows() if self._jobs else None
        step = self.steps
        if self.spec == "draft":
            # draft chain: k small-model decodes in one dispatch, writing
            # into the shadow pools.  The drafts stay device-resident and
            # feed verify directly; only the packed verify result crosses
            # to the host.
            with TraceAnnotation("repro.engine.draft", step=step):
                drafts, self._draft_cache = self._draft_fn(
                    self.draft_params, self._draft_cache,
                    self.state["token"], self.state["pos"],
                    self.state["block_tables"])
        with TraceAnnotation("repro.engine.decode", step=step):
            if self.spec == "draft":
                packed, self.state, self.active, self.budget = \
                    self._verify_fn(self.params, self.state, self.active,
                                    self.budget, drafts)
            else:
                packed, self.state, self.active, self.budget = \
                    self._step_fn(self.params, self.state, self.active,
                                  self.budget)
            if guard is not None:
                self._restore_rows(guard)
        self.steps += 1
        self.idle_slot_steps += self.slots - len(actives)
        with TraceAnnotation("repro.engine.readback", step=step):
            # lint: allow[one-transfer] -- THE single whitelisted device→host transfer per step (d2h_transfers counts it)
            out = jax.device_get(packed)
        self.d2h_transfers += 1
        if self.spec == "draft":
            acc, dones, tok_rows = out[0], out[1], out[2:]
        else:
            acc = np.ones((self.slots,), np.int64)
            toks, dones = out[0], out[1]
            tok_rows = toks[None]
        emitted = 0
        for si in actives:
            self._host_pos[si] += int(acc[si])
            emitted += int(acc[si])
        self._sample_kv_pressure()         # before evictions, as ever
        now = time.monotonic()
        for si in actives:
            meta = self.slot_meta[si]
            req = self._live[meta.rid]
            req.tokens.extend(int(tok_rows[s][si])
                              for s in range(int(acc[si])))
            if dones[si]:
                req.done_s = now - req.submitted
                self.done[req.rid] = req
                del self._live[meta.rid]
                self._evict_slot(si)
        if self.spec == "draft":
            self.spec_drafted += self.spec_k * len(actives)
            # of each slot's a committed tokens, a-1 were draft proposals
            # the target ratified; the last is the target's own bonus token
            self.spec_accepted += emitted - len(actives)
        self.tokens_emitted += emitted
        return emitted

    def warm_admission(self):
        """Stage every admission executable ahead of the first request:
        one jitted prefill trace per admit-length bucket, and (chunked
        mode) one chunk trace per possible chunk shape.  Chunk warming
        targets an all-scratch block-table row, so its writes land in the
        garbage block and no live state is disturbed.  Engines built by a
        serve image's factory share these jit wrappers, so a registry
        prefetch pays this once for every engine the image ever builds."""
        assert not self._live and not self._jobs, "warm on an idle engine"
        if self.role == "decode":
            return                     # no prefill executables to stage
        for pb in admit_buckets(self.max_len):
            logits, _ = self._prefill(
                self.params, {"tokens": jnp.zeros((1, pb), jnp.int32)})
            jax.block_until_ready(logits)
            if self.spec == "draft":
                # the draft bundle prefills once per admission too — stage
                # its trace for every bucket alongside the target's
                dlogits, _ = self._draft_prefill(
                    self.draft_params,
                    {"tokens": jnp.zeros((1, pb), jnp.int32)})
                jax.block_until_ready(dlogits)
        if self.prefill_mode == "chunked" and self._chunk_fn is not None:
            row = jnp.zeros((max(self.max_blocks_per_slot, 1),), jnp.int32)
            for C in prefill_chunk_shapes(self.max_len, self.block_size,
                                          self.prefill_chunk):
                logits, self.state = self._chunk_fn(
                    self.params, self.state,
                    jnp.zeros((1, C), jnp.int32), row,
                    jnp.int32(0), jnp.int32(0))
                jax.block_until_ready(logits)
            if self.cfg.ssm is not None:
                self._zero_ssm_rows(0)         # undo the warm's row scribble

    def warm_install(self):
        """Run one REAL admission + decode + eviction per admit bucket over
        dummy requests, then reset.  ``warm_admission`` stages the jitted
        prefill/chunk/step executables, but the admission INSTALL path
        (cache-row merge, paged block scatter, block-table writes, the
        packed-step unpack) is eager-dispatched — dozens of first-use op
        compiles that would otherwise land on the first live request's
        tick.  A fleet server must absorb that storm before taking leases:
        one stalled tick longer than the lease TTL makes the pool requeue
        everything the server just fetched."""
        assert not self._live and not self.queue and not self._jobs, \
            "warm on an idle engine"
        for i, pb in enumerate(admit_buckets(self.max_len)):
            try:
                # rid -1 is the free-slot sentinel and rejected by submit;
                # dummies start at -2
                if self.role == "decode":
                    # a decode-role engine admits via the import scatter,
                    # so its storm is warmed with synthetic handoffs
                    h = self._dummy_handoff(pb)
                    self.submit(Request(rid=-2 - i, prompt=h.prompt,
                                        max_new_tokens=1, handoff=h))
                else:
                    self.submit(Request(
                        rid=-2 - i,
                        prompt=(np.arange(pb) % self.cfg.vocab_size).astype(
                            np.int32),
                        max_new_tokens=1))
            except ValueError:
                continue                   # bucket exceeds this pool's reach
        self.run()
        if self.prefix is not None:
            # flush the dummies' published blocks: real prompts never match
            # the synthetic patterns, so leaving them cached would only pin
            # pool capacity and skew utilization stats from the first tick
            self.prefix.evict_unreferenced(self.allocator.capacity_blocks)
        self.reset_metrics()               # also drops the dummy results

    def block_leaks(self) -> int:
        """KV block-pool leak audit for an IDLE engine (nothing live,
        queued, or mid-admission): evicts the prefix cache's published
        (but unreferenced) blocks and returns how many pool blocks remain
        allocated — which must be zero if every admit/cancel/rollback path
        balanced its refcounts.  Chaos drills call this after drain on
        every server: hedged-loser cancels and stall revocations are
        exactly the paths that could strand a block."""
        if self.kv != "paged":
            return 0
        assert not self._live and not self.queue and not self._jobs, \
            "block_leaks() on a busy engine"
        if self.prefix is not None:
            self.prefix.evict_unreferenced(self.allocator.capacity_blocks)
        return self.allocator.allocated_blocks

    def kv_pool_bytes(self) -> dict:
        """KV cache memory: logical total and the per-device (local shard)
        footprint.  On a 1xN mesh the head-sharded pools put ~1/N of the
        pool bytes on each device — the capacity headroom TP buys."""
        total = local = 0
        for leaf in jax.tree.leaves(self.state["cache"]):
            total += int(leaf.nbytes)
            shards = getattr(leaf, "addressable_shards", None)
            local += int(shards[0].data.nbytes) if shards \
                else int(leaf.nbytes)
        return {"kv_pool_bytes": total, "kv_pool_bytes_per_device": local}

    def kv_pressure(self) -> dict:
        """Instantaneous cache-pressure sample for heartbeat telemetry:
        live/allocated RIGHT NOW (the `_stats` dict reports the mean over
        decode steps instead), so a pilot monitor sees a late-run pressure
        spike the moment it happens."""
        live = sum(self._host_pos[si]
                   for si, m in enumerate(self.slot_meta) if m.active)
        if self.kv == "paged":
            allocated = self.allocator.allocated_blocks * self.block_size
        else:
            allocated = self.slots * self.max_len
        return {
            "kv": self.kv,
            "role": self.role,
            "prefills_exported": self.prefills_exported,
            "handoffs_imported": self.handoffs_imported,
            "kv_memory_utilization": live / allocated if allocated else 0.0,
            "kv_live_tokens": live,
            "kv_peak_live_tokens": self.kv_peak_live_tokens,
            "kv_capacity_tokens": self.kv_capacity_tokens,
            # capacity accounting for the pool/autoscaler: a mesh-bound
            # server is ONE unit of `slots` capacity however many devices
            # back it; kv_capacity_tokens above is already per-mesh (the
            # pools are sharded, not replicated, across the mesh)
            "slots": self.slots,
            "mesh_devices": self.mesh_devices,
            "prefix_hit_rate": (self.prefix_hit_tokens
                                / self.prompt_tokens_total
                                if self.prompt_tokens_total else 0.0),
            # speculative effectiveness, live: the autoscaler reads these
            # to convert nominal slot capacity into EFFECTIVE token/step
            # capacity (a pool decoding 3 tokens/step needs fewer pilots)
            "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
            "tokens_per_step": (self.tokens_emitted / self.steps
                                if self.steps else 0.0),
        }

    def _sample_kv_pressure(self):
        live = sum(self._host_pos[si]
                   for si, m in enumerate(self.slot_meta) if m.active)
        if self.kv == "paged":
            allocated = self.allocator.allocated_blocks * self.block_size
        else:
            allocated = self.slots * self.max_len
        if allocated:
            self._kv_util_sum += live / allocated
        self.kv_peak_live_tokens = max(self.kv_peak_live_tokens, live)

    # ------------------------------------------------------------------

    def run(self, *, max_steps: int = 10_000) -> dict:
        t0 = time.monotonic()
        decoded = 0
        ticks = 0
        # prefill-role engines never take a decode step, so the safety
        # valve also counts raw ticks (admission/export work per tick)
        while ((self.queue or self._live or self._jobs)
               and self.steps < max_steps and ticks < max_steps):
            decoded += self.step()
            ticks += 1
        return self._stats(decoded, time.monotonic() - t0)

    def run_trace(self, trace, *, max_ticks: int = 100_000,
                  on_tick=None) -> dict:
        """Drive the engine from a request *trace* with staggered arrivals.

        ``trace`` is a list of JSON-able dicts (the startup-spec format the
        pilot system ships to a serve payload):
        ``{"rid", "prompt": [ints], "max_new_tokens", "at_step"}`` — the
        request becomes visible to admission at tick ``at_step``.  Idle
        ticks (waiting for an arrival) advance time but are not decode
        steps.

        ``on_tick(tick, step_seconds)`` (optional) runs after every tick —
        the wrapper's heartbeat/stop hook; returning False aborts the run.
        """
        pending = sorted(enumerate(trace),
                         key=lambda ie: int(ie[1].get("at_step", 0)))
        t0 = time.monotonic()
        decoded, tick, i = 0, 0, 0
        while i < len(pending) or self.queue or self._live or self._jobs:
            while i < len(pending) and int(pending[i][1].get("at_step", 0)) <= tick:
                idx, e = pending[i]
                i += 1
                self.submit(Request(
                    rid=int(e.get("rid", idx)),
                    prompt=np.asarray(e["prompt"], np.int32),
                    max_new_tokens=int(e.get("max_new_tokens", 16))))
            t_step = time.monotonic()
            decoded += self.step()
            tick += 1
            if on_tick is not None and on_tick(
                    tick, time.monotonic() - t_step) is False:
                break
            if tick >= max_ticks:
                break
        return self._stats(decoded, time.monotonic() - t0)

    def _stats(self, decoded: int, wall: float) -> dict:
        # occupancy, not throughput: with speculation a slot can commit
        # several tokens per step, so utilization counts slot-steps that
        # had a live request (identical to decoded/(steps*slots) when
        # spec == "off", where every live slot-step emits exactly one)
        denom = self.steps * self.slots
        util = (denom - self.idle_slot_steps) / denom if self.steps else 0.0
        ttfts = [r.first_token_s for r in self.done.values()
                 if r.first_token_s is not None]
        tpots = [(r.done_s - r.first_token_s) / max(1, len(r.tokens) - 1)
                 for r in self.done.values()
                 if r.done_s is not None and r.first_token_s is not None
                 and len(r.tokens) > 1]
        pct = lambda v, q: float(np.percentile(v, q)) if v else None
        return {
            "completed": len(self.done),
            "role": self.role,
            "prefills_exported": self.prefills_exported,
            "handoffs_imported": self.handoffs_imported,
            "decode_steps": self.steps,
            "tokens_decoded": decoded,
            "slot_utilization": util,
            "idle_slot_steps": self.idle_slot_steps,
            "d2h_transfers": self.d2h_transfers,
            "wall_s": wall,
            "tok_per_s": decoded / wall if wall else 0.0,
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "tpot_p50_s": pct(tpots, 50),
            "tpot_p99_s": pct(tpots, 99),
            # cache pressure (live tokens / allocated cache tokens, mean
            # over decode steps) + prefix-cache effectiveness
            "kv": self.kv,
            "kv_memory_utilization": (self._kv_util_sum / self.steps
                                      if self.steps else 0.0),
            "kv_peak_live_tokens": self.kv_peak_live_tokens,
            "kv_capacity_tokens": self.kv_capacity_tokens,
            "prefix_hit_rate": (self.prefix_hit_tokens
                                / self.prompt_tokens_total
                                if self.prompt_tokens_total else 0.0),
            "prefill_chunks": self.prefill_chunks,
            "blocked_admissions": self.blocked_admissions,
            "moe_rows": self.moe_rows,
            "moe_rows_run": self.moe_rows_run,
            # speculative decoding
            "spec": self.spec,
            "spec_k": self.spec_k if self.spec != "off" else 0,
            "spec_fallback_reason": self.spec_fallback_reason,
            "acceptance_rate": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
            "tokens_per_step": decoded / self.steps if self.steps else 0.0,
            # tensor-parallel footprint: shape None == single device;
            # per-device bytes < total is the memory headroom TP buys
            "mesh_shape": (tuple(self.mesh.devices.shape)
                           if self.mesh is not None else None),
            "mesh_devices": self.mesh_devices,
            "slots": self.slots,
            **self.kv_pool_bytes(),
        }

    def reset_metrics(self):
        """Zero the counters/results between benchmark phases (e.g. after a
        jit-warmup run) without touching compiled functions or slot state."""
        assert not self._live and not self.queue and not self._jobs, \
            "engine still has work"
        self.steps = 0
        self.idle_slot_steps = 0
        self.d2h_transfers = 0
        self.prefill_chunks = 0
        self.blocked_admissions = 0
        self.prefills_exported = 0
        self.handoffs_imported = 0
        self.prompt_tokens_total = 0
        self.prefix_hit_tokens = 0
        self._kv_util_sum = 0.0
        self.kv_peak_live_tokens = 0
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.tokens_emitted = 0
        self.moe_rows = 0
        self.moe_rows_run = 0
        if self.prefix is not None:
            self.prefix.lookups = 0
            self.prefix.hits = 0
        self.done.clear()


# --------------------------------------------------------------------------


def _install_slot(state, prefill_cache, slot: int, plen: int, next_token: int):
    """Copy one prefilled request's cache rows into batch row `slot` of the
    engine's shared DENSE decode state and reset that row's position to
    `plen`.  All LM cache leaves are stacked (n_groups/L, B, ...), so the
    batch dim is 1 everywhere."""
    new_cache = jax.tree.map(
        lambda dst, src: _merge_row(dst, src, slot),
        state["cache"], prefill_cache)
    token = state["token"].at[slot, 0].set(next_token)
    pos = state["pos"].at[slot].set(plen)
    return {"cache": new_cache, "token": token, "pos": pos}


def _merge_row(dst, src, slot):
    """Write prefill leaf `src` (groups, 1, T', ...) into row `slot` of the
    engine leaf `dst` (groups, B, T, ...)."""
    src_b = jnp.moveaxis(src, 1, 0)[0]           # drop batch (=1)
    dst_b = jnp.moveaxis(dst, 1, 0)              # (B, groups, ...)
    dst_b = dst_b.at[slot].set(
        _fit_rows(src_b, dst_b.shape[1:]).astype(dst.dtype))
    return jnp.moveaxis(dst_b, 0, 1)


def _install_slot_paged(state, prefill_cache, slot: int, plen: int,
                        next_token: int, row: list, nhit: int,
                        block_size: int):
    """Install a one-shot prefill into the PAGED decode state: scatter the
    dense prefill rows into the slot's freshly-allocated blocks (prefix-hit
    blocks already hold bit-identical content and are NOT written — that is
    the copy-free part of prefix reuse), write per-row leaves (SWA rings,
    SSM state) into batch row `slot`, and map the block-table row."""
    paged_keys = {"kp": "k", "vp": "v", "ckvp": "ckv", "kropep": "krope"}
    new_cache = []
    for st_leaf, pf_leaf in zip(state["cache"], prefill_cache):
        out = {}
        for key, val in st_leaf.items():
            if key in paged_keys:
                out[key] = _scatter_blocks(val, pf_leaf[paged_keys[key]],
                                           row, nhit, block_size)
            else:
                out[key] = _merge_row(val, pf_leaf[key], slot)
        new_cache.append(out)
    mb = state["block_tables"].shape[1]
    row_arr = np.zeros((mb,), np.int32)
    row_arr[:len(row)] = row
    return {
        "cache": new_cache,
        "token": state["token"].at[slot, 0].set(next_token),
        "pos": state["pos"].at[slot].set(plen),
        "block_tables": state["block_tables"].at[slot].set(
            jnp.asarray(row_arr)),
    }


def _install_draft_paged(cache, prefill_cache, row: list, nhit: int,
                         block_size: int):
    """Scatter a DRAFT-model prefill into the draft's shadow block pools at
    the same physical ids the target admission mapped.  Spec eligibility
    guarantees every draft cache leaf is paged (no SSM/SWA per-row state),
    so unlike `_install_slot_paged` there is no per-row merge arm."""
    paged_keys = {"kp": "k", "vp": "v", "ckvp": "ckv", "kropep": "krope"}
    new_cache = []
    for st_leaf, pf_leaf in zip(cache, prefill_cache):
        new_cache.append({
            key: _scatter_blocks(val, pf_leaf[paged_keys[key]],
                                 row, nhit, block_size)
            for key, val in st_leaf.items()})
    return new_cache


def _scatter_blocks(pool, src, row: list, nhit: int, block_size: int):
    """Scatter a dense prefill leaf (groups, 1, T', ...) into pool blocks
    (groups, nb, bs, ...) `row[nhit:]` (hit blocks are left untouched)."""
    rows = jnp.moveaxis(src, 1, 0)[0]            # (groups, T', ...)
    Tp = rows.shape[1]
    n_pb = -(-Tp // block_size)
    pad = n_pb * block_size - Tp
    if pad:
        spec = [(0, 0)] * rows.ndim
        spec[1] = (0, pad)
        rows = jnp.pad(rows, spec)
    rows = rows.reshape((rows.shape[0], n_pb, block_size) + rows.shape[2:])
    if nhit >= n_pb:
        return pool
    ids = jnp.asarray(np.asarray(row[nhit:n_pb], np.int32))
    return pool.at[:, ids].set(pool_rows(pool, rows[:, nhit:], 3))


def _host_row_shape(key: str, pool, cfg) -> tuple:
    """Per-position shape of pool ``key``'s KV handoff rows.  A GQA pool
    stores its heads merged in a lane-padded row; the handoff carries them
    split, (K, Dh), without the padding, so a handoff's bytes and format
    do not depend on the pool's layout.  Latent pools travel as stored."""
    if key in ("kp", "vp"):
        return (cfg.num_kv_heads, cfg.head_dim)
    return tuple(pool.shape[3:])


def _gather_blocks(cache, row: list, paged_keys, cfg) -> list:
    """Gather a slot's block chain out of every layer's paged pools into
    contiguous host buffers — the export half of the KV handoff.  The
    gather (``pool[:, ids]``) runs device-side; ONE ``device_get`` over
    the whole pytree then pulls every layer in a single host transfer."""
    ids = jnp.asarray(np.asarray(row, np.int32))

    def blocks(key, pool):
        shape = _host_row_shape(key, pool, cfg)
        b = pool[:, ids, :, :math.prod(shape)]
        return b.reshape(b.shape[:3] + shape)

    bufs = [{k: blocks(k, leaf[k]) for k in leaf if k in paged_keys}
            for leaf in cache]
    host = jax.device_get(bufs)
    return [{k: np.asarray(v) for k, v in leaf.items()} for leaf in host]


def _import_blocks_paged(state, bufs: list, slot: int, plen: int,
                         next_token: int, row: list, nhit: int,
                         block_size: int):
    """Scatter handoff buffers (per layer, ``(groups, n_pb, bs, ...)``,
    heads split as `_host_row_shape` gives them) into pool blocks
    ``row[nhit:n_pb]`` (prefix-hit blocks already hold
    bit-identical content) and install the slot's table row, token and
    position — the import half of the KV handoff, mirroring
    `_install_slot_paged` with host buffers in place of a prefill."""
    n_pb = -(-plen // block_size)
    new_cache = []
    ids = (None if nhit >= n_pb
           else jnp.asarray(np.asarray(row[nhit:n_pb], np.int32)))
    for st_leaf, hb in zip(state["cache"], bufs):
        out = dict(st_leaf)
        if ids is not None:
            for key, buf in hb.items():
                pool = st_leaf[key]
                out[key] = pool.at[:, ids].set(
                    pool_rows(pool, jnp.asarray(buf[:, nhit:]), 3))
        new_cache.append(out)
    mb = state["block_tables"].shape[1]
    row_arr = np.zeros((mb,), np.int32)
    row_arr[:len(row)] = row
    return {
        "cache": new_cache,
        "token": state["token"].at[slot, 0].set(next_token),
        "pos": state["pos"].at[slot].set(plen),
        "block_tables": state["block_tables"].at[slot].set(
            jnp.asarray(row_arr)),
    }


def _fit_rows(src, dst_shape):
    """Pad/crop the row dim of src (groups, T', ...) to dst (groups, T, ...)."""
    if src.shape == tuple(dst_shape):
        return src
    out = src
    for ax in range(len(dst_shape)):
        T, Tp = dst_shape[ax], out.shape[ax]
        if Tp > T:
            out = jax.lax.slice_in_dim(out, 0, T, axis=ax)
        elif Tp < T:
            pad = [(0, 0)] * out.ndim
            pad[ax] = (0, T - Tp)
            out = jnp.pad(out, pad)
    return out
