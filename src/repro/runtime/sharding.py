"""Logical->physical sharding rules (TP / FSDP / EP / sequence-parallel).

One table drives everything: each parameter leaf name maps to a
(tensor-parallel dim, FSDP dim) pair in *negative* indexing, which makes the
rules invariant to the scan-stacking group dim (and to MoE's expert dim for
up/gate/down, which share names with the dense MLP).

Divisibility is always checked: a dim is only sharded if the axis (product)
divides it; otherwise the rule degrades gracefully (FSDP tries
("pod","data") -> ("data",) -> ("pod",) -> replicate).  This is what lets a
single rule set serve all 10 assigned architectures (e.g. minicpm3's 40
heads don't divide model=16 -> its TP lands on latent ranks and d_ff
instead; gemma's single KV head is replicated).

Modes:
* "train"  — TP on the model axis + FSDP (ZeRO-3) over the batch axes for
             params AND optimizer moments; batch over ("pod","data").
* "serve"  — TP only; params replicated over batch axes; decode caches are
             sequence-sharded over "model" (flash-decode split-K) and
             batch-sharded over ("pod","data").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, GetAttrKey, SequenceKey

from repro.runtime.mesh import DATA_AXIS, MODEL_AXIS, POD_AXIS


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= axis_size(mesh, n)
        return out
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def batch_axes(mesh: Mesh, layout: str = "2d"):
    """Physical axes carrying the batch.  layout "fsdp" folds the model axis
    into the batch/FSDP dimension (no tensor parallelism) — the right call
    for archs whose head counts don't divide the model axis (replicated
    attention under TP) and whose optimizer state fits when sharded over all
    chips."""
    pool = ((POD_AXIS, DATA_AXIS, MODEL_AXIS) if layout == "fsdp"
            else (POD_AXIS, DATA_AXIS))
    axes = tuple(a for a in pool if a in mesh.axis_names)
    return axes if axes else None


def _fsdp_candidates(mesh: Mesh, layout: str = "2d"):
    cands = []
    ba = batch_axes(mesh, layout)
    if ba:
        cands.append(ba)
        if len(ba) > 2:
            cands.append(ba[:2])
            cands.append(ba[1:])
        for a in ba:
            cands.append((a,))
    return cands


def _choose_fsdp(mesh: Mesh, dim_size: int, layout: str = "2d"):
    for cand in _fsdp_candidates(mesh, layout):
        if dim_size % axis_size(mesh, cand) == 0:
            return cand if len(cand) > 1 else cand[0]
    return None


def _maybe(mesh: Mesh, axis, dim_size: int):
    return axis if (axis in mesh.axis_names and dim_size % axis_size(mesh, axis) == 0) else None


# --------------------------------------------------------------------------
# parameter rules: name -> (tp_dim, fsdp_dim), negative indices
# --------------------------------------------------------------------------

_PARAM_RULES: dict[str, tuple[int | None, int | None]] = {
    "embed":    (-2, -1),   # (V, D): vocab over model, D FSDP
    "head":     (-1, -2),   # (D, V)
    "wq":       (-2, -3),   # (..., D, H, Dh)
    "wk":       (-2, -3),
    "wv":       (-2, -3),
    "wo":       (-3, -1),   # (..., H, Dh, D)
    "wq_a":     (-1, -2),   # (..., D, r)
    "wq_b":     (-2, -3),   # (..., r, H, k)
    "wkv_a":    (-1, -2),
    "wkv_b":    (-2, -3),
    "up":       (-1, -2),   # dense (..., D, F) and MoE (..., E, D, F)
    "gate":     (-1, -2),
    "down":     (-2, -1),   # dense (..., F, D) and MoE (..., E, F, D)
    "router":   (None, -2),
    "in_proj":  (-1, -2),   # (..., D, Z)
    "out_proj": (-2, -1),   # (..., d_inner, D)
    "conv_w":   (-1, None),
    "conv_b":   (-1, None),
}

_MOE_NAMES = ("up", "gate", "down")


def _leaf_name(path) -> str:
    for k in reversed(path):
        if isinstance(k, DictKey):
            return str(k.key)
        if isinstance(k, GetAttrKey):
            return str(k.name)
    return ""


def _is_moe_leaf(path, ndim: int, name: str) -> bool:
    # MoE up/gate/down are 3-D (+1 stacked group dim = 4-D); dense are 2/3-D
    if name not in _MOE_NAMES:
        return False
    return ndim == (4 if _stacked(path) else 3)


def _stacked(path) -> bool:
    """True if the leaf lives under the scanned layer stack."""
    return any(isinstance(k, DictKey) and str(k.key) in
               ("layers", "enc_layers", "dec_layers") for k in path)


def param_spec(path, shape, mesh: Mesh, mode: str, *,
               moe_partition: str = "tp", layout: str = "2d") -> P:
    name = _leaf_name(path)
    ndim = len(shape)
    if name not in _PARAM_RULES or ndim == 0:
        return P()
    tp_dim, fsdp_dim = _PARAM_RULES[name]
    spec: list = [None] * ndim

    def put(dim, axis):
        if dim is None or axis is None:
            return
        if -dim > ndim:
            return
        if spec[dim % ndim] is None:
            spec[dim % ndim] = axis

    if layout != "fsdp":
        if moe_partition == "ep" and _is_moe_leaf(path, ndim, name):
            e_dim = -3
            if mode == "serve":
                # decode weight streaming: experts over the (idle) data
                # axis AND expert hidden over model — combined E*F sharding
                if shape[e_dim % ndim] % axis_size(mesh, DATA_AXIS) == 0:
                    put(e_dim, DATA_AXIS)
                if tp_dim is not None and -tp_dim <= ndim:
                    put(tp_dim, _maybe(mesh, MODEL_AXIS, shape[tp_dim % ndim]))
            # train: experts over the model axis (token all-to-all dispatch)
            elif shape[e_dim % ndim] % axis_size(mesh, MODEL_AXIS) == 0:
                put(e_dim, MODEL_AXIS)
        else:
            if tp_dim is not None and -tp_dim <= ndim:
                put(tp_dim, _maybe(mesh, MODEL_AXIS, shape[tp_dim % ndim]))
    if mode == "train" and fsdp_dim is not None and -fsdp_dim <= ndim:
        if spec[fsdp_dim % ndim] is None:
            put(fsdp_dim, _choose_fsdp(mesh, shape[fsdp_dim % ndim], layout))
    return P(*spec)


def param_shardings(param_specs_tree, mesh: Mesh, mode: str, *,
                    moe_partition: str = "tp", layout: str = "2d"):
    """param_specs_tree: pytree of ShapeDtypeStruct (or arrays)."""
    def one(path, leaf):
        return NamedSharding(
            mesh, param_spec(path, leaf.shape, mesh, mode,
                             moe_partition=moe_partition, layout=layout))
    return jax.tree_util.tree_map_with_path(one, param_specs_tree)


# --------------------------------------------------------------------------
# batch / decode-state rules
# --------------------------------------------------------------------------

def _batch_dim_axis(mesh: Mesh, b: int, layout: str = "2d"):
    ba = batch_axes(mesh, layout)
    if not ba:
        return None
    if b % axis_size(mesh, ba) == 0:
        return ba if len(ba) > 1 else ba[0]
    if len(ba) > 2:
        for cand in (ba[:2], ba[1:]):
            if b % axis_size(mesh, cand) == 0:
                return cand
    for a in ba:
        if b % axis_size(mesh, a) == 0:
            return a
    return None


def batch_shardings(batch_specs, mesh: Mesh, layout: str = "2d"):
    """tokens/targets (B,S) -> batch over (pod,data); frontend (B,F,D) same."""
    def one(path, leaf):
        spec = [None] * len(leaf.shape)
        spec[0] = _batch_dim_axis(mesh, leaf.shape[0], layout)
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, batch_specs)


def decode_state_shardings(state_specs, mesh: Mesh):
    """Decode caches: batch dim over (pod,data); the long sequence dim (self-
    attn KV / MLA latent) over "model" (split-K); SSM state heads over
    "model".  Leaf kinds are identified structurally by name."""
    def one(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        ndim = len(shape)
        spec: list = [None] * ndim
        if name == "pos":
            return NamedSharding(mesh, P())
        if name == "token":
            spec[0] = _batch_dim_axis(mesh, shape[0])
            return NamedSharding(mesh, P(*spec))
        # cache leaves: possibly stacked (n_groups first).  Identify batch dim
        # as the dim right after the stack dim (if stacked) else dim 0.
        bdim = 1 if _stacked_cache(path) else 0
        if ndim > bdim:
            spec[bdim] = _batch_dim_axis(mesh, shape[bdim])
        if name in ("k", "v", "ckv", "krope"):
            tdim = bdim + 1
            if ndim > tdim and shape[tdim] % axis_size(mesh, MODEL_AXIS) == 0:
                spec[tdim] = MODEL_AXIS
        elif name == "ssd":                      # (..., B, H, N, P)
            hdim = bdim + 1
            if ndim > hdim and shape[hdim] % axis_size(mesh, MODEL_AXIS) == 0:
                spec[hdim] = MODEL_AXIS
        elif name == "conv":                     # (..., B, W-1, conv_dim)
            cdim = bdim + 2
            if ndim > cdim and shape[cdim] % axis_size(mesh, MODEL_AXIS) == 0:
                spec[cdim] = MODEL_AXIS
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, state_specs)


def _stacked_cache(path) -> bool:
    """Cache pytrees: a list of per-slot dicts whose leaves carry the group
    dim first (decoder caches), or dicts under "self"/"cross" (encdec, leading
    layer dim)."""
    for k in path:
        if isinstance(k, SequenceKey):
            return True
        if isinstance(k, DictKey) and str(k.key) in ("self", "cross"):
            return True
    return False


def serve_state_shardings(state_specs, mesh: Mesh):
    """Serve-engine decode state under tensor parallelism: KV pools shard on
    the HEAD dim over "model", never on the sequence/block dim.

    This is deliberately different from :func:`decode_state_shardings`
    (split-K over the sequence dim): splitting the KV sequence changes the
    attention reduction order and breaks the engine's bitwise
    sharded-vs-single-device parity guarantee.  Splitting heads keeps every
    per-head softmax+weighted-sum bitwise identical to the single-device
    kernel — each shard owns whole heads.

    Rules (dims in trailing/negative indexing, stacked group dim invariant):
      kp/vp       (nb, bs, K, Dh)        -> K (dim -2) over "model"
      ckvp        (nb, bs, r_latent)     -> latent (dim -1) over "model"
      kropep      (nb, bs, d_rope)       -> latent (dim -1) over "model"
      k/v dense   (B, T, K, Dh)          -> K (dim -2) over "model"
      ckv/krope dense (B, T, r)          -> latent (dim -1) over "model"
      ssd/conv / token / pos / block_tables -> replicated
    Every rule degrades to replication when the axis doesn't divide the dim.
    """
    msz = axis_size(mesh, MODEL_AXIS)

    def one(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        ndim = len(shape)
        spec: list = [None] * ndim
        if msz > 1 and ndim >= 2:
            if name in ("kp", "vp"):
                if shape[-2] % msz == 0:
                    spec[ndim - 2] = MODEL_AXIS
            elif name in ("ckvp", "kropep"):
                if shape[-1] % msz == 0:
                    spec[ndim - 1] = MODEL_AXIS
            elif name in ("k", "v"):
                if ndim >= 3 and shape[-2] % msz == 0:
                    spec[ndim - 2] = MODEL_AXIS
            elif name in ("ckv", "krope"):
                if shape[-1] % msz == 0:
                    spec[ndim - 1] = MODEL_AXIS
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, state_specs)


_SERVE_TP_SAFE = frozenset(
    {"embed", "head", "wq", "wk", "wv", "wq_b", "wkv_b", "up", "gate"})


def serve_param_shardings(tree, mesh: Mesh):
    """Order-preserving tensor parallelism for the serve engine.

    Only COLUMN-parallel weights shard — those whose TP dim is an *output*
    dim of the forward contraction (wq/wk/wv/up/gate/... split heads or
    d_ff; the contraction dim D/r stays whole on every shard, so each
    shard's outputs are bitwise identical to the single-device slices).
    ROW-parallel weights (wo, down, out_proj: TP dim is the contraction
    dim) are deliberately replicated: sharding them turns the contraction
    into partial sums combined by psum, whose reduction order differs from
    the single-device einsum and flips argmax on near-tie logits — which
    breaks the engine's bitwise sharded-vs-single-device token parity
    guarantee.  wq_a/wkv_a are also replicated (their outputs feed rmsnorm
    over the latent dim, a reduction that must not be sharded).

    The memory win that matters for serving — the paged KV pools — comes
    from :func:`serve_state_shardings`, not from here.
    """
    def one(path, leaf):
        name = _leaf_name(path)
        ndim = len(leaf.shape)
        if name not in _SERVE_TP_SAFE or ndim == 0:
            return NamedSharding(mesh, P())
        tp_dim, _ = _PARAM_RULES[name]
        spec: list = [None] * ndim
        if tp_dim is not None and -tp_dim <= ndim:
            spec[tp_dim % ndim] = _maybe(
                mesh, MODEL_AXIS, leaf.shape[tp_dim % ndim])
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, tree)


def serve_param_shard_factor(path, shape, model_axis_size: int) -> int:
    """How many ways :func:`serve_param_shardings` would split this leaf
    on a mesh with ``model_axis_size`` model shards — as a PURE divisor,
    no Mesh or devices required.  Mirrors the sharding rules exactly
    (column-parallel leaves only, divisibility-gated, else replicated),
    so a dry run can account per-device serve memory without building
    the mesh it is sizing for."""
    name = _leaf_name(path)
    ndim = len(shape)
    if model_axis_size <= 1 or name not in _SERVE_TP_SAFE or ndim == 0:
        return 1
    tp_dim, _ = _PARAM_RULES[name]
    if tp_dim is None or -tp_dim > ndim:
        return 1
    return (model_axis_size
            if shape[tp_dim % ndim] % model_axis_size == 0 else 1)


def serve_state_shard_factor(path, shape, model_axis_size: int) -> int:
    """Pure-divisor mirror of :func:`serve_state_shardings`: KV pools and
    dense caches split on the head/latent dim over the model axis when it
    divides, everything else (ssd/conv/token/pos/block_tables) replicates."""
    name = _leaf_name(path)
    ndim = len(shape)
    msz = model_axis_size
    if msz <= 1 or ndim < 2:
        return 1
    if name in ("kp", "vp"):
        return msz if shape[-2] % msz == 0 else 1
    if name in ("ckvp", "kropep"):
        return msz if shape[-1] % msz == 0 else 1
    if name in ("k", "v"):
        return msz if (ndim >= 3 and shape[-2] % msz == 0) else 1
    if name in ("ckv", "krope"):
        return msz if shape[-1] % msz == 0 else 1
    return 1


def replicated(tree, mesh: Mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


# --------------------------------------------------------------------------
# train-state assembly
# --------------------------------------------------------------------------

def train_state_shardings(param_specs_tree, mesh: Mesh, *,
                          moe_partition: str = "tp", layout: str = "2d"):
    ps = param_shardings(param_specs_tree, mesh, "train",
                         moe_partition=moe_partition, layout=layout)
    return {
        "params": ps,
        "opt": {
            "m": ps,
            "v": ps,
            "step": NamedSharding(mesh, P()),
        },
    }


# --------------------------------------------------------------------------
# activation sharding constraints (MaxText-style)
# --------------------------------------------------------------------------
# XLA's sharding propagation loses the batch axis inside the BACKWARD
# while-loop of grad(checkpoint(scan(...))) — cotangents and remat recompute
# then run with a replicated batch (measured: 260x the ideal per-device
# FLOPs on smollm train_4k).  The production fix is explicit
# with_sharding_constraint on activations inside the scan body; these
# helpers are no-ops unless a mesh context is active, so model code stays
# pure for tests/smoke runs.

import threading as _threading
from contextlib import contextmanager

_ACT = _threading.local()


@contextmanager
def activation_sharding(mesh: Mesh, layout: str = "2d"):
    prev = getattr(_ACT, "ctx", None)
    _ACT.ctx = (mesh, layout)
    try:
        yield
    finally:
        _ACT.ctx = prev


def active_serve_mesh() -> Mesh | None:
    """The mesh of the enclosing "serve" :func:`activation_sharding`
    context, or None.  Read at TRACE time — model code uses it to pick
    head-sharded kernel dispatch (shard_map over the head axis) without
    carrying a mesh through every call signature."""
    ctx = getattr(_ACT, "ctx", None)
    return ctx[0] if ctx is not None and ctx[1] == "serve" else None


def constrain_replicated(x):
    """Pin an activation fully replicated — fires only under a "serve"
    layout context (the serve engine's SPMD step/prefill traces).

    Placed immediately BEFORE every contraction whose reduction dim can be
    sharded (the wo out-projection over heads, the MLP down over d_ff, MLA
    score/out math over gathered latents): forces GSPMD to all-gather the
    operand and run the reduction whole on every device — the same
    canonical order as the single-device engine — instead of the cheaper
    partial-sum + psum, whose low-bit differences flip argmax on near-tie
    logits and break bitwise token parity."""
    ctx = getattr(_ACT, "ctx", None)
    if ctx is None or ctx[1] != "serve":
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx[0], P()))


def constrain(x, dims: str):
    """Constrain an activation if a mesh context is active.

    ``dims`` has one char per array dim:
      'b' -> batch axes (pod+data, +model under the "fsdp" layout)
      'm' -> model axis (tensor-parallel dim; skipped under "fsdp")
      'd' -> data axis (serve-mode expert parallelism)
      '.' -> unconstrained
    Axes are applied only when they divide the dim size (graceful degrade,
    same rule as the parameter table).  Conflicting axis use (e.g. batch and
    experts both wanting "data") skips the constraint.
    """
    ctx = getattr(_ACT, "ctx", None)
    if ctx is None:
        return x
    mesh, layout = ctx
    assert len(dims) == x.ndim, (dims, x.shape)
    spec = []
    for ch, size in zip(dims, x.shape):
        if ch == "b":
            spec.append(_batch_dim_axis(mesh, size, layout))
        elif ch == "m" and layout != "fsdp":
            spec.append(_maybe(mesh, MODEL_AXIS, size))
        elif ch == "d":
            spec.append(_maybe(mesh, DATA_AXIS, size))
        else:
            spec.append(None)
    flat = []
    for s in spec:
        if s is not None:
            flat.extend(s if isinstance(s, tuple) else (s,))
    if len(flat) != len(set(flat)):     # conflicting axes -> skip
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
