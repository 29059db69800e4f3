"""PayloadWrapper — the startup wrapper inside the payload container (§3.5).

Responsibilities, mirroring the paper:

1. runs as fake-root inside the payload container: it may set up the
   environment and register processes, but it *drops privileges* before
   invoking user code — the user step loop only ever sees a
   :class:`PayloadCapability` with the payload uid and the shared arena
   path (never the pilot's private area or the pod-patch capability);
2. sources the payload environment from the shared volume;
3. runs the payload and relays its exit code + telemetry back through
   ``exitcode.json`` on the shared volume (there is no parent-child process
   relationship to propagate it through);
4. heartbeats per step so the pilot's monitor can meter progress and
   enforce limits at step boundaries.
"""

from __future__ import annotations

import dataclasses
import time
import traceback

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.arena import SharedArena
from repro.core.proctable import PAYLOAD_UID, ProcessTable


@dataclasses.dataclass(frozen=True)
class PayloadCapability:
    """What user code gets after the privilege drop: its uid and the shared
    volume path.  No pilot token, no private volume, no pod patch rights."""
    uid: int
    shared_dir: str


_KEY_CACHE: dict[int, object] = {}


def _seed_key(seed: int):
    """jax.random.key costs ~3 ms of dispatch per call — dominant in the
    control-plane cost of a tiny payload.  Keys are pure functions of the
    seed, so memoize (bounded: payload seeds are few)."""
    k = _KEY_CACHE.get(seed)
    if k is None:
        if len(_KEY_CACHE) > 128:
            _KEY_CACHE.clear()
        k = _KEY_CACHE[seed] = jax.random.key(seed)
    return k


def run_wrapper(arena: SharedArena, proctable: ProcessTable, exe, spec: dict):
    """Execute one payload under the payload uid.  Never raises: every
    outcome becomes an exit code in the arena (the paper's relay)."""
    # env arrives inside the startup spec (the pilot path) or, for direct
    # arena users, in the standalone env file on the shared volume (§3.5)
    env = spec.get("env")
    if env is None:
        env = arena.read_env()
    entry = proctable.register(PAYLOAD_UID, f"payload:{exe.image.arch}:{exe.image.mode}")
    cap = PayloadCapability(uid=PAYLOAD_UID, shared_dir=arena.shared)
    t_start = time.monotonic()
    telemetry: dict = {"steps": 0, "mode": exe.image.mode,
                       "arch": exe.image.arch, "step_times": []}
    exitcode = 0
    try:
        key = _seed_key(int(env.get("seed", 0)))
        n_steps = int(spec.get("n_steps", 1))
        if exe.image.mode == "noop":
            exe.fn(exe.make_inputs(key))
            telemetry["steps"] = 1
        elif exe.image.mode == "train":
            exitcode = _train_loop(exe, key, n_steps, entry, proctable,
                                   telemetry, spec, arena, cap)
        elif exe.image.mode == "prefill":
            params, batch = exe.make_inputs(key, telemetry)
            t0 = time.monotonic()
            logits, cache = exe.fn(params, batch)
            jax.block_until_ready(logits)
            dt = time.monotonic() - t0
            proctable.heartbeat(entry.pid, dt)
            telemetry["steps"] = 1
            telemetry["step_times"].append(dt)
            if not np.isfinite(np.asarray(logits, np.float32)).all():
                exitcode = 3
        elif exe.image.mode == "serve":
            exitcode = _serve_loop(exe, key, n_steps, entry, proctable,
                                   telemetry, spec)
        else:                                           # decode
            params, state = exe.make_inputs(key, telemetry)
            for i in range(n_steps):
                if entry.stop.is_set():
                    exitcode = 143                      # SIGTERM-by-pilot
                    break
                t0 = time.monotonic()
                logits, state = exe.fn(params, state)
                jax.block_until_ready(logits)
                dt = time.monotonic() - t0
                proctable.heartbeat(entry.pid, dt)
                telemetry["steps"] = i + 1
                telemetry["step_times"].append(dt)
    except Exception:                                    # noqa: BLE001
        exitcode = 1
        telemetry["error"] = traceback.format_exc(limit=-8)
    telemetry["wall"] = time.monotonic() - t_start
    telemetry["step_times"] = telemetry["step_times"][-16:]
    proctable.mark_exited(entry.pid, exitcode)
    arena.report_exit(exitcode, telemetry)
    return exitcode


def _serve_loop(exe, key, n_steps, entry, proctable, telemetry, spec) -> int:
    """Serve payload: a continuous-batching inference server late-bound onto
    the slice.

    Two request sources, selected by the startup spec:

    * ``trace`` — the single-engine path: JSON dicts ``{"rid", "prompt":
      [ints], "max_new_tokens", "at_step"}``; a request is admitted once the
      engine has ticked ``at_step`` times (staggered arrivals).
    * ``dispatch`` — the FLEET path: the spec names a
      :class:`~repro.serving.dispatch.FleetDispatcher` pool and the server
      leases requests out of it instead of owning a static trace; per-
      request progress piggybacks on lease renewal every tick, so a server
      that dies simply stops renewing and its in-flight requests requeue
      onto survivors (see ``_fleet_serve_loop``).

    ``n_steps`` bounds the tick count — the lease/budget contract serve
    shares with train.  The engine's decode loop is device-resident (one
    device→host transfer per step); each tick heartbeats the proctable so
    the pilot's monitor meters serve progress exactly as it meters train
    steps.
    """
    task_id = spec.get("task_id")
    with TraceAnnotation("repro.payload.weights", task_id=task_id):
        params = exe.make_inputs(key, telemetry)
    kv_kw = {k: spec[k] for k in ("kv", "prefill", "prefill_chunk",
                                  "num_blocks", "block_size",
                                  "prefix_sharing", "spec", "spec_k",
                                  "mesh_shape", "role")
             if spec.get(k) is not None}
    with TraceAnnotation("repro.payload.engine_build", task_id=task_id):
        eng = exe.fn(params, slots=spec.get("slots"),
                     max_len=spec.get("max_len"), **kv_kw)
    if spec.get("dispatch"):
        return _fleet_serve_loop(eng, spec, n_steps, entry, proctable,
                                 telemetry)

    def on_tick(tick, dt):
        if entry.stop.is_set():
            return False                                # SIGTERM-by-pilot
        proctable.heartbeat(entry.pid, dt)
        telemetry["steps"] = tick
        telemetry["step_times"].append(dt)
        # live cache-pressure sample rides every heartbeat, so the pilot's
        # monitor sees KV pressure mid-run, not only at exit
        telemetry["serve_live"] = eng.kv_pressure()
        return True

    stats = eng.run_trace(spec.get("trace") or [], max_ticks=n_steps,
                          on_tick=on_tick)
    if entry.stop.is_set():
        return 143
    # cache pressure rides along: the pilot's heartbeat consumer sees how
    # hot the slot-sized claim is running and what the prefix cache saves
    telemetry["serve"] = {k: stats[k] for k in _SERVE_STAT_KEYS}
    telemetry["tokens"] = {str(r.rid): r.tokens for r in eng.done.values()}
    return 0


_SERVE_STAT_KEYS = (
    "completed", "decode_steps", "tokens_decoded", "slot_utilization",
    "idle_slot_steps", "d2h_transfers", "tok_per_s",
    "ttft_p50_s", "ttft_p99_s",
    "kv", "kv_memory_utilization", "kv_peak_live_tokens",
    "kv_capacity_tokens", "prefix_hit_rate", "prefill_chunks",
    "blocked_admissions", "moe_rows", "moe_rows_run",
    "spec", "spec_fallback_reason", "acceptance_rate", "tokens_per_step",
    "mesh_shape", "mesh_devices", "slots",
    "kv_pool_bytes", "kv_pool_bytes_per_device",
    "role", "prefills_exported", "handoffs_imported")


def _fleet_serve_loop(eng, spec, n_steps, entry, proctable, telemetry) -> int:
    """Fleet serve: lease requests from the pool named in the startup spec
    instead of replaying a static trace.

    Per tick: top up free slots from the pool (the fetch parks on the pool
    condition when the engine is idle, so a requeued request wakes the
    server immediately), one engine step, report completions (first
    completion wins at the pool), then renew every in-flight lease with its
    progress.  A renewal the pool refuses means the lease expired and moved
    elsewhere — the slot is cancelled rather than racing a replay it cannot
    win.

    Death semantics: when the stop event fires (node loss / SIGTERM) the
    loop returns WITHOUT releasing anything — a dead server cannot clean up,
    and the pool's lease-expiry reaper requeueing its in-flight requests is
    exactly the failure path this payload exists to exercise.  A graceful
    end (tick budget, pool closed, or the pilot's DRAIN event — the
    autoscaler's scale-down path) hands unfinished requests straight back
    instead: survivors requeue them immediately, no lease-TTL wait.

    Each tick also reports the engine's KV-pressure sample to the pool
    (``report_telemetry``), which the autoscaler reads via
    ``pool_pressure`` — kv_memory_utilization / blocked_admissions are
    scale-up signals a queue-depth-only policy would miss."""
    from repro.core import chaos
    from repro.serving import dispatch as fleet_dispatch
    from repro.serving.engine import Request

    pool = fleet_dispatch.get_pool(spec["dispatch"])
    if pool is None:
        raise RuntimeError(f"fleet pool {spec['dispatch']!r} is not "
                           f"registered in this process")
    server_id = ((spec.get("env") or {}).get("pilot")
                 or f"server-{spec.get('task_id', id(eng))}")
    labels = spec.get("server_labels") or {}
    # stage every admission bucket AND the whole admit/decode/evict install
    # path before taking the first lease: a mid-serve compile stalls
    # renewals past the lease TTL and thrashes requests between servers
    # that are all compiling.  Factory-shared jit wrappers and the
    # process-global eager-op cache make this nearly free for every server
    # after the first on the same image.
    eng.warm_admission()
    eng.warm_install()
    # labels carry the server's pool role ({"pool": "prefill"|"decode"}) so
    # pool_pressure() can report per-label telemetry instead of blending
    # prefill TTFT with decode TPOT across a mixed fleet
    pool.announce(server_id, labels=labels)
    inflight: dict[int, Request] = {}
    fetched = completed_here = released = 0
    decoded = tick = 0
    t_start = time.monotonic()
    while tick < n_steps:
        if entry.stop.is_set():
            return 143                   # died mid-serve: leases just expire
        if pool.closed.is_set():
            break
        if entry.drain.is_set():
            break        # scale-down: wind down NOW — leased work is
                         # released below, not left to wait out its TTL
        # chaos drills (no-op dict probe when no controller is installed):
        # a STALLED payload freezes — no fetch, no step, no completions —
        # but its lease renewals keep flowing with frozen progress, which
        # is exactly the gray failure only the progress watchdog can see
        site = chaos.site(server_id)
        stalled = site is not None and site.stalled()
        cut = site is not None and site.partitioned()
        if stalled:
            if inflight:
                pool.renew(server_id, {rid: len(r.tokens)
                                       for rid, r in inflight.items()})
            time.sleep(0.005)
            tick += 1
            continue
        # _live already counts mid-admission (_jobs) requests, so this is
        # every admitted-or-queued request exactly once
        want = eng.slots - (len(eng._live) + len(eng.queue))
        if want > 0 and not cut and not pool.finished():
            idle = not any(m.active for m in eng.slot_meta) and not eng._jobs
            for e in pool.fetch(server_id, max_n=want,
                                timeout=0.05 if idle else 0.0,
                                labels=labels, cancel=entry.stop.is_set):
                if (site is not None and e.get("poison")
                        and site.poison_lethal()):
                    # poison request: detonates on fetch, killing this
                    # pilot — the lease is never released; it expires and
                    # the pool's blast-radius accounting takes over
                    site.trip_poison(int(e["rid"]))
                    return 143
                req = Request(
                    rid=int(e["rid"]),
                    prompt=np.asarray(e["prompt"], np.int32),
                    max_new_tokens=int(e.get("max_new_tokens", 16)),
                    submitted=float(e.get("submitted_s", time.monotonic())),
                    handoff=e.get("handoff"))
                if req.rid in inflight:
                    # the pool re-leased a rid this server still holds
                    # locally: its lease expired mid-partition and looped
                    # back before this tick's renew could reveal the loss.
                    # Purge the stale copy — pairing the fresh Request
                    # with the old engine result would commit truncated
                    # tokens (and two live slots under one rid is worse)
                    eng.cancel(req.rid)
                    inflight.pop(req.rid, None)
                eng.done.pop(req.rid, None)    # stale result of a lost lease
                try:
                    eng.submit(req)
                except ValueError:
                    pool.reject(server_id, req.rid)   # can NEVER fit here
                    continue
                inflight[req.rid] = req
                fetched += 1
        t0 = time.monotonic()
        decoded += eng.step()
        dt = time.monotonic() - t0
        if site is not None:
            slow = site.slow_factor()
            if slow > 1.0:               # straggler: inflate the step time
                time.sleep(dt * (slow - 1.0))
                dt = dt * slow
        tick += 1
        proctable.heartbeat(entry.pid, dt)
        telemetry["steps"] = tick
        telemetry["step_times"].append(dt)
        if cut:
            # control-plane partition: the payload keeps computing but
            # renewals, completions and telemetry cannot reach the pool.
            # Leases expire and the work replays elsewhere; completions
            # parked in eng.done are reported after the partition heals
            # (first completion wins keeps it exactly once either way).
            if pool.finished() and not inflight:
                break
            continue
        for rid in [r for r in inflight if r in eng.done]:
            req = inflight.pop(rid)
            # a prefill-role engine attaches the exported KV handoff; the
            # pool's on_complete hook (DisaggRouter) forwards it into the
            # decode stage.  Unified engines complete with handoff=None.
            if pool.complete(server_id, rid, req.tokens,
                             first_token_s=req.first_token_s,
                             handoff=req.handoff):
                completed_here += 1
        if inflight:
            lost = pool.renew(server_id, {rid: len(r.tokens)
                                          for rid, r in inflight.items()})
            for rid in lost:
                eng.cancel(rid)          # re-leased elsewhere: free the slot
                inflight.pop(rid, None)
        # the heartbeat consumer sees cache pressure AND per-request
        # progress — renewals piggyback on the same tick; the same sample
        # goes to the pool, where the autoscaler reads it as a demand signal
        live_sample = {
            **eng.kv_pressure(),
            "blocked_admissions": eng.blocked_admissions,
            "free_slots": eng.slots - (len(eng._live) + len(eng.queue)),
        }
        if not (site is not None and site.drop_heartbeat()):
            pool.report_telemetry(server_id, live_sample)
        telemetry["serve_live"] = {
            **live_sample,
            "inflight": {str(rid): len(r.tokens)
                         for rid, r in inflight.items()}}
        if pool.finished() and not inflight:
            break
    if inflight:                         # graceful end with work leased:
        drained = eng.drain_requests()   # give it back, don't sit on it
        pool.release(server_id, [r.rid for r in drained])
        released = len(drained)
        inflight.clear()
    pool.retire(server_id)               # gone capacity must not look live
    stats = eng._stats(decoded, time.monotonic() - t_start)
    telemetry["serve"] = {k: stats[k] for k in _SERVE_STAT_KEYS}
    telemetry["serve"]["fleet"] = {
        "server_id": server_id, "pool": pool.name, "fetched": fetched,
        "completed_here": completed_here, "released": released,
        "drained": entry.drain.is_set(),
        # leak audit on the now-idle engine: every cancel/hedge-loser/
        # revocation path must have returned its KV blocks to the pool
        "leaked_blocks": eng.block_leaks()}
    telemetry["tokens"] = {str(r.rid): r.tokens for r in eng.done.values()}
    return 0


def _train_loop(exe, key, n_steps, entry, proctable, telemetry, spec, arena,
                cap) -> int:
    """Train payload: supports checkpoint-based resume (fault tolerance)."""
    from repro.ckpt import checkpoint as ck

    state, data = exe.make_inputs(key)
    start_step = 0
    ckpt_dir = spec.get("ckpt_dir")
    ckpt_every = int(spec.get("ckpt_every", 0))
    if ckpt_dir:
        latest = ck.latest_step(ckpt_dir)
        if latest is not None:
            state = ck.restore(ckpt_dir, latest, state)
            start_step = latest
            telemetry["resumed_from"] = latest
    losses = []
    for i in range(start_step, n_steps):
        if entry.stop.is_set():
            return 143
        t0 = time.monotonic()
        batch = {k: jax.numpy.asarray(v) for k, v in data.batch_at(i).items()}
        state, metrics = exe.fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.monotonic() - t0
        proctable.heartbeat(entry.pid, dt)
        telemetry["steps"] = i + 1 - start_step
        telemetry["step_times"].append(dt)
        losses.append(loss)
        if not np.isfinite(loss):
            return 3
        if ckpt_dir and ckpt_every and (i + 1) % ckpt_every == 0:
            ck.save(ckpt_dir, i + 1, state)
    telemetry["first_loss"] = losses[0] if losses else None
    telemetry["last_loss"] = losses[-1] if losses else None
    if ckpt_dir and losses:
        ck.save(ckpt_dir, n_steps, state)
    return 0
