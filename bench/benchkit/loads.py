"""Drive the system under test through its own path: cluster -> pilot ->
image -> engine.  One load generator per kind of mix; a mix file names
its kind.

* ``open_loop``   — one pilot runs one serve image as a fleet server; the
  client submits through ``FleetDispatcher.submit`` on a Poisson schedule
  and stamps each request with the time it was DUE, so a late generator
  shows up as latency, never as a fast server.
* ``closed_loop`` — the same server; ``clients`` requests stay
  outstanding, each completion lets that client send its next one.
* ``rebind``      — serve tasks go in through ``TaskRepo.submit`` to one
  pilot that already holds its slice, alternating between images of
  different serve shape, so every task is an image swap (a late bind).

Each returns a :class:`Run`: per-request records the readers and
the comparison use, the window's length, and what set-up cost.
"""

from __future__ import annotations

import dataclasses
import threading
import time

from benchkit import traffic

WARM_RID0 = 1 << 30          # warm-up requests' ids, apart from the window's
# A fleet server's lease on a request outlives any tick: a tick stalled by
# admissions is latency, never a death that requeues the request.
LEASE_TTL_S = 60.0
# How long after the window the loops wait for the requests still in
# flight; a request unanswered by then counts as failed.
DRAIN_S = 120.0


@dataclasses.dataclass
class Run:
    kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    binds: list = dataclasses.field(default_factory=list)
    window_tokens: int = 0
    lag_max_s: float = 0.0
    compiles_setup: int = 0
    compiles_window: int = 0
    memory_peak_bytes: int = 0


class CompileMeter:
    """Backend compiles, counted from JAX's own compile-duration events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self.n += 1

    def take(self) -> int:
        with self._lock:
            n, self.n = self.n, 0
        return n


class Window:
    """The measured window: optional profiler trace around it."""

    def __init__(self, run: Run, meter: CompileMeter, trace_dir: str | None):
        self.run, self.meter, self.trace_dir = run, meter, trace_dir

    def __enter__(self):
        import jax
        self.run.compiles_setup = self.meter.take()
        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.t0 = time.monotonic()
        return self

    def close(self):
        """End the window (idempotent): stop tracing, count compiles."""
        if self.run.window_s:
            return
        import jax
        self.run.window_s = time.monotonic() - self.t0
        if self.trace_dir:
            jax.profiler.stop_trace()
        self.run.compiles_window = self.meter.take()

    def __exit__(self, *exc):
        self.close()


def _memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def serve_image(cfg: dict, engine: dict):
    from repro.core.images import PayloadImage
    prog = cfg["program"]
    return PayloadImage(
        prog["arch"], f"custom:{engine['max_len']}x{engine['slots']}",
        "serve", smoke=bool(prog.get("smoke", False)),
        flags=tuple((k, v) for k, v in prog.get("flags", [])))


def _vocab(cfg: dict) -> int:
    return int(cfg["model"]["vocab_size"])


# --------------------------------------------------------------------------
# fleet server: open and closed loops
# --------------------------------------------------------------------------

class _Server:
    """One pilot running one serve image as a fleet server."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.core.cluster import ClusterSim
        from repro.core.pilot import PilotConfig
        from repro.serving.dispatch import FleetDispatcher
        eng = mix["engine"]
        self.sim = ClusterSim()
        self.pool = FleetDispatcher(lease_ttl=LEASE_TTL_S)
        (slice_,) = self.sim.provision(1)
        self.pilot = self.sim.spawn_pilot(
            slice_, PilotConfig(max_payloads=1, idle_grace=5.0))
        self.tid = self.sim.repo.submit(
            serve_image(cfg, eng), n_steps=1 << 40, max_wall=1e6,
            max_attempts=1, env={"seed": seed},
            payload_spec={"dispatch": self.pool.name, "slots": eng["slots"],
                          "max_len": int(eng["max_len"])})
        if not self.pool.wait_servers(1, timeout=1100.0):
            raise RuntimeError(f"serve payload never came up: "
                               f"{self.pilot.history or self.pilot.error}")

    def settled(self) -> int:
        """Requests of the window that completed or failed."""
        st = self.pool.stats()
        return st["completed"] + st["failed"] - self.warm_n

    def warm(self, mix: dict, seed: int, vocab: int):
        """Serve the mix's warm-up requests (set-up, not measured)."""
        self.warm_n = int(mix.get("warmup_requests", 0))
        for e in traffic.requests(mix, seed + 1, self.warm_n, vocab,
                                  rid0=WARM_RID0):
            self.pool.submit(e)
        if not self.pool.wait_completed(self.warm_n, timeout=600.0):
            raise RuntimeError("warm-up requests did not complete")

    def tokens_so_far(self) -> int:
        return sum(len(r.tokens) if r.tokens is not None else r.progress
                   for rid, r in self.pool.records().items()
                   if rid < WARM_RID0)

    def finish(self, grace_s: float) -> bool:
        self.pool.seal()
        return self.pool.wait_all(timeout=grace_s)

    def close(self):
        self.pool.close()
        self.sim.join_all(timeout=60.0)

    def records(self, window_rids: set) -> list[dict]:
        out = []
        for rid, r in sorted(self.pool.records().items()):
            if rid not in window_rids:
                continue
            e = r.entry
            out.append({
                "rid": rid, "prompt": e["prompt"],
                "output_len": e["output_len"], "due_s": r.submitted_s,
                "first_token_s": r.first_token_s,
                "completed_s": r.completed_s,
                "tokens": list(r.tokens) if r.tokens is not None else None,
                "failed": bool(r.failed or r.tokens is None)})
        return out


def open_loop(cfg, mix, seed, seconds, meter, trace_dir) -> Run:
    t_setup = time.monotonic()
    run = Run("open_loop")
    vocab = _vocab(cfg)
    srv = _Server(cfg, mix, seed)
    try:
        srv.warm(mix, seed, vocab)
        due = traffic.schedule(mix, seed, seconds)
        reqs = traffic.requests(mix, seed, len(due), vocab)
        run.setup_s = time.monotonic() - t_setup
        with Window(run, meter, trace_dir) as win:
            for off, e in zip(due, reqs):
                at = win.t0 + float(off)
                while (now := time.monotonic()) < at:
                    time.sleep(min(at - now, 0.002))
                run.lag_max_s = max(run.lag_max_s, now - at)
                srv.pool.submit({**e, "submitted_s": at})
            while time.monotonic() < win.t0 + seconds:
                time.sleep(0.005)
            win.close()
            srv.finish(DRAIN_S)
        run.memory_peak_bytes = _memory_peak()
        run.requests = srv.records({e["rid"] for e in reqs})
    finally:
        srv.close()
    return run


def closed_loop(cfg, mix, seed, seconds, meter, trace_dir) -> Run:
    t_setup = time.monotonic()
    run = Run("closed_loop")
    vocab = _vocab(cfg)
    clients = int(mix["clients"])
    srv = _Server(cfg, mix, seed)
    try:
        srv.warm(mix, seed, vocab)
        reqs = traffic.requests(mix, seed, int(mix["requests"]), vocab)
        run.setup_s = time.monotonic() - t_setup
        sent = 0
        with Window(run, meter, trace_dir) as win:
            while time.monotonic() < win.t0 + seconds:
                while sent - srv.settled() < clients:
                    if sent == len(reqs):
                        raise RuntimeError("mix ran out of requests; raise "
                                           "'requests'")
                    srv.pool.submit({**reqs[sent],
                                     "submitted_s": time.monotonic()})
                    sent += 1
                srv.pool.wait_completed(srv.settled() + srv.warm_n + 1,
                                        timeout=0.01)
            run.window_tokens = srv.tokens_so_far()
            win.close()
            srv.finish(DRAIN_S)
        run.memory_peak_bytes = _memory_peak()
        run.requests = srv.records({e["rid"] for e in reqs[:sent]})
    finally:
        srv.close()
    return run


# --------------------------------------------------------------------------
# rebind: one pilot, serve tasks that swap images
# --------------------------------------------------------------------------

class _FirstTick:
    """Proctable observer: when each payload's first engine tick ended, by
    the benchmark's clock.  The first tick admits the request, and its
    admission prefill emits the first token."""

    def __init__(self):
        self.first: dict[int, float] = {}
        self.lock = threading.Lock()

    def __call__(self, kind, entry):
        from repro.core.proctable import PAYLOAD_UID
        if kind != "step" or entry.uid != PAYLOAD_UID:
            return
        with self.lock:
            self.first.setdefault(entry.pid, time.monotonic())

    def since(self, t: float) -> float | None:
        with self.lock:
            hits = [v for v in self.first.values() if v >= t]
        return min(hits) if hits else None


def _history_record(pilot, tid: int, timeout_s: float = 60.0) -> dict:
    """The pilot's history record of task ``tid`` ({} if none comes)."""
    end = time.monotonic() + timeout_s
    while True:
        for rec in reversed(pilot.history):
            if rec.get("task_id") == tid:
                return rec
        if time.monotonic() > end:
            return {}
        time.sleep(0.001)


def rebind(cfg, mix, seed, seconds, meter, trace_dir) -> Run:
    from repro.core.cluster import ClusterSim
    from repro.core.pilot import PilotConfig
    t_setup = time.monotonic()
    run = Run("rebind")
    vocab = _vocab(cfg)
    images = [serve_image(cfg, eng) for eng in mix["images"]]
    sim = ClusterSim()
    (slice_,) = sim.provision(1)
    pilot = sim.spawn_pilot(slice_, PilotConfig(max_payloads=1 << 30,
                                                idle_grace=600.0))
    ticks = _FirstTick()
    pilot.proctable.subscribe(ticks)

    def bind(i: int, req: dict) -> dict:
        img = images[i % len(images)]
        t_sub = time.monotonic()
        tid = sim.repo.submit(img, n_steps=1 << 20, max_wall=600.0,
                              max_attempts=1, env={"seed": seed},
                              payload_spec={"trace": [req]})
        while (res := sim.repo.result(tid)) is None:
            if tid in sim.repo.failed_tasks():
                break
            time.sleep(0.001)
        # the pilot completes the task before it cleans up and appends the
        # payload's history record: wait for this task's own record
        rec = _history_record(pilot, tid)
        tel = res.telemetry if res is not None else {}
        first = ticks.since(t_sub)
        toks = tel.get("tokens", {}).get(str(req["rid"]))
        ok = (res is not None and res.exitcode == 0 and first is not None
              and toks is not None)
        ttft = first - t_sub if ok else None
        return {"task_id": tid, "image": img.shape, "t_submit": t_sub,
                "bind_ttft_s": ttft,
                "pull_s": rec.get("bind_seconds"), "tokens": toks,
                "rid": req["rid"], "prompt": req["prompt"],
                "output_len": req["output_len"], "failed": not ok}

    try:
        warm = traffic.requests(mix, seed + 1, int(mix["setup_binds"]),
                                vocab, rid0=WARM_RID0)
        for i, req in enumerate(warm):
            if bind(i, req)["failed"]:
                raise RuntimeError(f"set-up bind failed: {pilot.history}")
        reqs = traffic.requests(mix, seed, int(mix["requests"]), vocab)
        run.setup_s = time.monotonic() - t_setup
        with Window(run, meter, trace_dir) as win:
            i = 0
            # the window closes after a whole round of images, so every
            # run's mean holds each image equally often
            while time.monotonic() < win.t0 + seconds or i % len(images):
                if i == len(reqs):
                    raise RuntimeError("mix ran out of requests; raise "
                                       "'requests'")
                run.binds.append(bind(i, reqs[i]))
                i += 1
        run.memory_peak_bytes = _memory_peak()
    finally:
        pilot.drain()
        sim.repo.kick()
        sim.join_all(timeout=60.0)
    run.requests = [{"rid": b["rid"], "prompt": b["prompt"],
                     "output_len": b["output_len"], "tokens": b["tokens"],
                     "failed": b["failed"]} for b in run.binds]
    return run


LOADS = {"open_loop": open_loop, "closed_loop": closed_loop,
         "rebind": rebind}
