"""PayloadImage + ExecutableRegistry — container images and the image cache.

A *PayloadImage* names everything needed to build the payload's executable:
(architecture x input shape x step kind x flags).  "Pulling" an image is XLA
compilation against the slice's mesh; the registry's cache plays the node's
local image cache — a warm ``bind()`` skips compilation exactly as a cached
image skips the pull (measured in benchmarks/bind_latency.py).

The PLACEHOLDER image is the paper's arbitrary default container image: a
trivial executable every slice can always run, installed at pod creation so
the Kubernetes-side object is valid before any payload exists (§3.3).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis.locks import make_lock
from repro.configs.base import ArchConfig, SHAPES, ShapeSpec, get_config, get_smoke_config
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models.api import build_model
from repro.optim.adamw import OptimConfig
from repro.runtime import sharding as shd


@dataclasses.dataclass(frozen=True)
class PayloadImage:
    """Immutable image reference (the `image:` field of the pod spec)."""
    arch: str                        # registry name, or "<name>-smoke"
    shape: str                       # key into SHAPES, or "smoke"
    mode: str                        # "train" | "prefill" | "decode" | "serve" | "noop"
    smoke: bool = True               # reduced config (tests/examples) vs full
    flags: tuple = ()                # e.g. (("remat","dots"), ("attn_impl","causal_blocked"))
    # serve mode only: registry name of a DRAFT model for speculative
    # decoding.  Like the arch itself, the draft choice is a late-binding
    # decision — it names a different image (own compile-cache key), and
    # engines from the image default to spec="draft" with this draft.
    draft: str | None = None
    # serve mode only: SPMD device-mesh shape ``(data, model)`` the image's
    # engines run on (None = single device).  Mesh shape is a LATE-BINDING
    # decision exactly like the arch: a pilot claims devices first, and the
    # mesh-shaped executable binds after — so it is part of ``key()`` and
    # the registry compiles/warms once per (image, mesh).
    mesh_shape: tuple | None = None
    # serve mode only: the engine's serving ROLE in a disaggregated fleet
    # ("prefill" | "decode" | "unified").  Role is a late-binding decision
    # exactly like the arch — a pilot claims a slice first and the role
    # shapes which step fns the image compiles — so it is part of ``key()``
    # and a prefill-only image never pays the decode-step compile.
    role: str = "unified"

    def key(self) -> tuple:
        return (self.arch, self.shape, self.mode, self.smoke, self.flags,
                self.draft, self.mesh_shape, self.role)

    def build_mesh(self):
        """The serve mesh this image requests, or None (single device)."""
        if self.mesh_shape is None:
            return None
        from repro.runtime.mesh import serve_mesh
        return serve_mesh(self.mesh_shape)

    def config(self) -> ArchConfig:
        cfg = get_smoke_config(self.arch) if self.smoke else get_config(self.arch)
        if self.flags:
            cfg = dataclasses.replace(cfg, **dict(self.flags))
        return cfg

    def shape_spec(self) -> ShapeSpec:
        if self.shape in SHAPES:
            return SHAPES[self.shape]
        if self.shape.startswith("custom:"):        # "custom:<seq>x<batch>"
            seq, batch = self.shape.split(":", 1)[1].split("x")
            return ShapeSpec(self.shape, int(seq), int(batch), self.mode)
        # smoke shapes: tiny, CPU-runnable
        mode = "train" if self.mode == "train" else self.mode
        return ShapeSpec("smoke", 64, 2, mode)


PLACEHOLDER = PayloadImage(arch="placeholder", shape="none", mode="noop")


@dataclasses.dataclass
class Executable:
    """A pulled image: compiled function + input builders."""
    image: PayloadImage
    fn: Any                           # jitted/compiled callable
    # (key) -> concrete input pytree; the serve, prefill and decode kinds
    # take ``telemetry=`` too and record ``weights_compiles`` in it
    make_inputs: Any
    compile_seconds: float
    cached: bool = False
    # force the lazy XLA compile now (one representative invocation);
    # None for modes whose compile cannot be staged ahead (serve engines
    # jit per instance).  prefetch() runs this in the background so the
    # whole pull — python build AND XLA compile — overlaps the current
    # payload instead of landing on the next bind's first step.
    warm: Any = None


class ExecutableRegistry:
    """Compile cache keyed by (image, mesh shape).  Thread-safe; one compile
    per key even under concurrent binds (single-flight)."""

    def __init__(self):
        self._lock = make_lock("images.registry")
        self._cache: dict[tuple, Executable] = {}
        self._inflight: dict[tuple, threading.Event] = {}
        self._prefetching: dict[tuple, threading.Event] = {}
        self.stats = {"hits": 0, "misses": 0, "prefetches": 0}

    @staticmethod
    def _key(image: PayloadImage, mesh) -> tuple:
        return (image.key(), None if mesh is None else
                (tuple(mesh.devices.shape), tuple(mesh.axis_names)))

    def prefetch(self, image: PayloadImage, mesh=None) -> threading.Event:
        """Start pulling an image in the BACKGROUND and return an event that
        is set once it is cached.  Single-flight with `pull`: a concurrent
        bind for the same key waits on the same compile instead of starting
        a second one, and a later `pull` that lands mid-compile parks on the
        inflight event and then takes the cache hit.

        This is how a pilot overlaps the next task's image pull with the
        current payload's run (the hint rides on the matched task) — the
        late-binding analogue of a kubelet pre-pulling the next image while
        the current container still executes.
        """
        key = self._key(image, mesh)
        with self._lock:
            ev = self._prefetching.get(key)
            if ev is not None:                # join the in-progress prefetch:
                return ev                     # set only after warm() finishes
            done = threading.Event()
            if key in self._cache:
                done.set()
                return done
            # claim the key under the lock so concurrent prefetches of the
            # same image join `done` instead of spawning a second worker
            self._prefetching[key] = done
            self.stats["prefetches"] += 1

        def work():
            try:
                # pull() joins any concurrent bind's compile (single-flight)
                exe = self.pull(image, mesh)
                if exe.warm is not None:
                    exe.warm()            # stage the lazy XLA compile too
            except Exception:             # noqa: BLE001 — prefetch is a hint
                pass
            finally:
                with self._lock:
                    self._prefetching.pop(key, None)
                done.set()

        threading.Thread(target=work, daemon=True,
                         name=f"prefetch-{image.arch}:{image.mode}").start()
        return done

    def pull(self, image: PayloadImage, mesh=None) -> Executable:
        key = self._key(image, mesh)
        while True:
            with self._lock:
                if key in self._cache:
                    self.stats["hits"] += 1
                    e = self._cache[key]
                    return Executable(e.image, e.fn, e.make_inputs,
                                      e.compile_seconds, cached=True,
                                      warm=e.warm)
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    break
            ev.wait()                    # another bind is compiling this image
        try:
            exe = self._build(image, mesh)
            with self._lock:
                self._cache[key] = exe
                self.stats["misses"] += 1
            return exe
        finally:
            with self._lock:
                ev = self._inflight.pop(key)
            ev.set()

    # ------------------------------------------------------------------

    def _build(self, image: PayloadImage, mesh) -> Executable:
        t0 = time.monotonic()
        if image.mode == "noop":
            fn = jax.jit(lambda x: x + 1.0)
            fn(jnp.zeros(()))            # warm
            return Executable(image, fn, lambda key: jnp.zeros(()),
                              time.monotonic() - t0)

        cfg = image.config()
        shape = image.shape_spec()
        bundle = build_model(cfg)
        # weights from the seed for the prefill, decode and serve kinds:
        # one staged program, compiled by warm() (or the first bind) and
        # dispatched once by every bind, which still gets fresh buffers
        # from its own key
        init = jax.jit(bundle.init)

        def weights(key, telemetry=None):
            n = init._cache_size()
            params = init(key)
            if telemetry is not None:     # 0 on every bind after warm()
                telemetry["weights_compiles"] = init._cache_size() - n
            return params

        warm = None
        if image.mode == "train":
            step = make_train_step(cfg, OptimConfig(total_steps=1000))
            fn = jax.jit(step, donate_argnums=0)

            def make_inputs(key):
                from repro.launch.steps import init_train_state
                from repro.data.synthetic import SyntheticConfig, SyntheticLM
                state = init_train_state(cfg, key)
                data = SyntheticLM(SyntheticConfig(
                    cfg.vocab_size, _text_len(cfg, shape.seq_len),
                    shape.global_batch))
                return state, data

            def warm():
                state, data = make_inputs(jax.random.key(0))
                batch = {k: jnp.asarray(v)
                         for k, v in data.batch_at(0).items()}
                jax.block_until_ready(fn(state, batch)[1]["loss"])
        elif image.mode == "prefill":
            step = make_prefill_step(cfg)
            fn = jax.jit(step)

            def make_inputs(key, telemetry=None):
                params = weights(key, telemetry)
                batch = _concrete_batch(cfg, shape, key, with_targets=False)
                return params, batch

            def warm():
                jax.block_until_ready(fn(*make_inputs(jax.random.key(0)))[0])
        elif image.mode == "serve":
            # a serve image is an ENGINE factory: the wrapper builds a
            # continuous-batching ServeEngine over freshly-initialized params
            # and drives it either from the request trace in the startup
            # spec or — when the spec names a fleet pool ("dispatch") — by
            # leasing requests out of a FleetDispatcher, where a dead
            # server's in-flight requests requeue onto survivors.
            # Every engine from this factory shares ONE jitted step (per
            # max_len), ONE jitted prefill wrapper and ONE chunked-prefill
            # wrapper, so warm() can stage the XLA compiles at prefetch time
            # and the payload's first tick hits the cache; params come from
            # the image's seed, so every server in a fleet serves IDENTICAL
            # weights — what makes replay-from-prompt reproduce a dead
            # server's tokens bitwise.
            from repro.serving.engine import (
                ServeEngine, _traced_under_mesh, make_draft_step,
                make_engine_step, make_verify_step,
            )

            # the image's requested serve mesh (late binding: the slice's
            # devices are already held; this shapes the executable over
            # them).  One mesh per factory — a different mesh_shape is a
            # different image key, so the registry keeps the compiles apart.
            eng_mesh = image.build_mesh()
            step_fns: dict[int, Any] = {}
            prefill_fn = jax.jit(_traced_under_mesh(bundle.prefill,
                                                    eng_mesh))
            chunk_fn = (jax.jit(_traced_under_mesh(bundle.prefill_chunk,
                                                   eng_mesh),
                        donate_argnums=1)
                        if bundle.prefill_chunk is not None else None)
            # the draft model is part of the image: one bundle, one fixed-
            # seed param set and one jitted prefill shared by every engine
            # the factory builds — so a fleet's servers draft (and replay)
            # bitwise-identically, and a registry prefetch stages the draft
            # compiles alongside the target's
            draft_cfg = draft_bundle = draft_prefill_fn = None
            draft_params_cache: dict[str, Any] = {}
            if image.draft:
                draft_cfg = (get_smoke_config(image.draft) if image.smoke
                             else get_config(image.draft))
                draft_bundle = build_model(draft_cfg)
                draft_prefill_fn = jax.jit(
                    _traced_under_mesh(draft_bundle.prefill, eng_mesh))
            spec_fns: dict[tuple, Any] = {}

            def step_for(max_len):
                if max_len not in step_fns:
                    step_fns[max_len] = make_engine_step(bundle, max_len,
                                                         mesh=eng_mesh)
                return step_fns[max_len]

            def spec_for(max_len, k):
                if (max_len, k) not in spec_fns:
                    spec_fns[(max_len, k)] = (
                        make_draft_step(draft_bundle or bundle, k, max_len,
                                        mesh=eng_mesh),
                        make_verify_step(bundle, max_len, k, mesh=eng_mesh))
                return spec_fns[(max_len, k)]

            def draft_params_for():
                if "params" not in draft_params_cache:
                    draft_params_cache["params"] = draft_bundle.init(
                        jax.random.key(0))
                return draft_params_cache["params"]

            def fn(params, slots=None, max_len=None, mesh_shape=None, **kw):
                ml = max_len or shape.seq_len
                mesh = eng_mesh
                shared = True
                kw.setdefault("role", image.role)
                role = kw["role"]
                if mesh_shape is not None \
                        and tuple(mesh_shape) != image.mesh_shape:
                    # startup-spec override of the image's mesh: correct
                    # but unprefetched — the engine jits its own steps for
                    # the off-image geometry (first tick pays the compile)
                    from repro.runtime.mesh import serve_mesh
                    mesh = serve_mesh(tuple(mesh_shape))
                    shared = False
                if image.draft and role == "unified":
                    # non-unified roles force spec off (draft KV does not
                    # ride the handoff) — don't stage draft fns they drop
                    kw.setdefault("spec", "draft")
                if kw.get("spec") == "draft":
                    kw.setdefault("spec_k", 4)
                    if shared:
                        dfn, vfn = spec_for(ml, int(kw["spec_k"]))
                        kw.setdefault("draft_fn", dfn)
                        kw.setdefault("verify_fn", vfn)
                    if draft_bundle is not None:
                        kw.setdefault("draft_cfg", draft_cfg)
                        kw.setdefault("draft_bundle", draft_bundle)
                        kw.setdefault("draft_params", draft_params_for())
                        if shared:
                            kw.setdefault("draft_prefill_fn",
                                          draft_prefill_fn)
                return ServeEngine(cfg, params,
                                   slots=slots or shape.global_batch,
                                   max_len=ml, bundle=bundle,
                                   step_fn=(step_for(ml)
                                            if shared and role != "prefill"
                                            else None),
                                   prefill_fn=prefill_fn if shared else None,
                                   chunk_fn=chunk_fn if shared else None,
                                   mesh=mesh, **kw)

            make_inputs = weights

            def warm():
                # build a throwaway engine THROUGH the factory so the
                # staged shapes (KV layout, pool size, buckets, chunk
                # shapes) are exactly what served engines will use — the
                # jit wrappers are shared, so every compile lands in the
                # caches production engines hit.  Specs that override
                # engine geometry (num_blocks/block_size/prefill_chunk)
                # trade this prewarm for a first-tick compile.
                params = weights(jax.random.key(0))
                eng = fn(params, prefill="chunked")
                eng.warm_admission()   # buckets + chunk shapes (+ draft);
                #                        no-op for a decode-role engine
                if eng.role == "prefill":
                    return             # exports at admission — the decode
                #                        step never runs on this image
                if eng.spec == "draft":
                    # stage the draft-chain and k-position verify compiles
                    # (the decode loop a speculative engine actually runs)
                    drafts, eng._draft_cache = eng._draft_fn(
                        eng.draft_params, eng._draft_cache,
                        eng.state["token"], eng.state["pos"],
                        eng.state["block_tables"])
                    out = eng._verify_fn(params, eng.state, eng.active,
                                         eng.budget, drafts)
                else:
                    out = eng._step_fn(params, eng.state, eng.active,
                                       eng.budget)  # the decode-step compile
                jax.block_until_ready(out[0])
        else:                            # decode
            step = make_serve_step(cfg)
            fn = jax.jit(step, donate_argnums=1)

            def make_inputs(key, telemetry=None):
                from repro.models.api import init_decode_state
                params = weights(key, telemetry)
                state = init_decode_state(cfg, shape.global_batch,
                                          shape.seq_len)
                return params, state

            def warm():
                jax.block_until_ready(fn(*make_inputs(jax.random.key(0)))[0])

        return Executable(image, fn, make_inputs, time.monotonic() - t0,
                          warm=warm)


def _text_len(cfg, seq_len):
    return seq_len - cfg.frontend_tokens if cfg.family == "vlm" else seq_len


def _concrete_batch(cfg, shape, key, *, with_targets=True):
    B = shape.global_batch
    S = _text_len(cfg, shape.seq_len)
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if with_targets:
        batch["targets"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    if cfg.family in ("vlm", "audio"):
        batch["frontend"] = jax.random.normal(
            ks[2], (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16) * 0.02
    return batch
