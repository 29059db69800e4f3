"""Batched-serving driver THROUGH the pilot system.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --requests 16 --slots 4 [--wave] [--via-pilots] \
      [--pilots N [--fail-at K]]

Default runs the continuous-batching engine directly on a staggered-arrival
trace, at the model's published widths unless ``--smoke`` (``--wave``
selects the static wave-batching baseline for comparison).
``--via-pilots`` submits inference servers as ``serve`` payloads: each
engine run — trace and all — is late-bound onto a pilot-held slice, and a
second model is served by the SAME pilot right after (the multi-payload
demo).  The first task carries a prefetch hint for the second image, so its
compile overlaps the first server's run.  It exits non-zero unless the
repo drains and every payload exits 0.

Every pilot mode here (``--via-pilots``, ``--pilots``, ``--disagg``,
``--autoscale``) builds SMOKE images: ``PayloadImage(shape="smoke")`` with
the reduced config, CPU-sized.  ``chip_smoke.py`` at the repo root drives
the same pilot path at published widths (``smoke=False``,
``shape="custom:<seq>x<batch>"``, ``flags=(("attn_impl", "pallas"),)``).

``--pilots N`` runs the FLEET serve demo: the trace is split into
per-request leases in a FleetDispatcher pool and N pilots each run a server
that pulls from it.  ``--fail-at K`` hard-kills a lease-holding pilot once K
requests have completed — its in-flight requests requeue onto the survivors
and the trace still reaches 100% completion.

``--autoscale`` replays the trace as a bursty square-wave arrival schedule
under the demand-driven autoscaler (``core/autoscaler.py``): the fleet
grows from queue pressure, shrinks to zero in the gaps, and re-provisions
on the next burst.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from repro.configs.base import get_config, get_smoke_config
from repro.core.cluster import ClusterSim
from repro.core.images import PayloadImage
from repro.core.pilot import PilotConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import build_model
from repro.serving.dispatch import FleetDispatcher
from repro.serving.engine import ServeEngine


def make_trace(vocab_size: int, n_requests: int, *, max_len: int = 128,
               stagger: int = 1, seed: int = 0,
               dup_rate: float = 0.0) -> list[dict]:
    """Staggered-arrival request trace (the startup-spec format): request i
    becomes visible at engine tick ``i * stagger``, with mixed prompt
    lengths and token budgets.  ``dup_rate`` is the fraction of requests
    that repeat an earlier prompt verbatim (the repeated-query pattern the
    paged engine's prefix cache serves copy-free)."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n_requests):
        if trace and rng.random() < dup_rate:
            prompt = list(trace[int(rng.integers(0, len(trace)))]["prompt"])
        else:
            plen = int(rng.integers(4, max(5, max_len // 4)))
            prompt = rng.integers(0, vocab_size, size=plen).tolist()
        trace.append({
            "rid": i,
            "prompt": prompt,
            "max_new_tokens": int(rng.choice([6, 10, 18, 28])),
            "at_step": i * stagger,
        })
    return trace


def serve_direct(cfg, n_requests: int, slots: int, max_len: int,
                 seed: int = 0, admission: str = "continuous",
                 kv: str | None = None, prefill: str = "oneshot",
                 num_blocks: int | None = None,
                 dup_rate: float = 0.0, spec: str = "off", spec_k: int = 4,
                 draft_cfg=None, mesh_shape=None) -> dict:
    mesh = None
    if mesh_shape is not None:
        from repro.runtime.mesh import serve_mesh
        mesh = serve_mesh(mesh_shape)
    params = build_model(cfg).init(jax.random.key(seed))
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      admission=admission, kv=kv, prefill=prefill,
                      num_blocks=num_blocks, spec=spec, spec_k=spec_k,
                      draft_cfg=draft_cfg, mesh=mesh)
    trace = make_trace(cfg.vocab_size, n_requests, max_len=max_len,
                       seed=seed, dup_rate=dup_rate)
    return eng.run_trace(trace)


def serve_via_pilots(archs: list[str], n_requests: int = 8,
                     n_steps: int = 400, slots: int | None = None,
                     max_len: int | None = None) -> int:
    """Several inference servers (different models!) multiplexed over ONE
    pilot — container late-binding for serving.  Task i hints task i+1's
    image so the pilot prefetches the next compile during the current run.

    Returns the process exit code: 0 only if the repo drained and every
    payload exited 0."""
    sim = ClusterSim()
    images = [PayloadImage(arch=a, shape="smoke", mode="serve") for a in archs]
    tids = []
    for i, (a, img) in enumerate(zip(archs, images)):
        cfg = get_smoke_config(a)
        # None = the image's factory geometry (shape spec) — which is also
        # what a prefetch warm() stages, so the default demo hits the
        # prefetched compile; explicit flags override both
        eff_max_len = max_len or img.shape_spec().seq_len
        trace = make_trace(cfg.vocab_size, n_requests, max_len=eff_max_len,
                           seed=i)
        hint = images[i + 1] if i + 1 < len(images) else None
        tids.append(sim.repo.submit(
            img, n_steps=n_steps, prefetch_hint=hint,
            payload_spec={"trace": trace, "max_len": max_len,
                          "slots": slots}))
    (s,) = sim.provision(1)
    pilot = sim.spawn_pilot(s, PilotConfig(max_payloads=len(archs) + 1,
                                           idle_grace=2.0))
    ok = sim.run_until_drained(timeout=600.0)
    sim.join_all(timeout=30.0)
    print(f"[serve] drained={ok} repo={sim.repo.stats()} "
          f"registry={sim.registry.stats}")
    exitcodes = [rec.get("exitcode") for rec in pilot.history]
    for i, (tid, arch) in enumerate(zip(tids, archs)):
        r = sim.repo.result(tid)
        if r:
            sv = r.telemetry.get("serve", {})
            print(f"  {arch}: completed={sv.get('completed')} "
                  f"util={sv.get('slot_utilization', 0):.2f} "
                  f"tok/s={sv.get('tok_per_s', 0):.1f} "
                  f"ttft_p50={sv.get('ttft_p50_s')} "
                  f"(bind cached={pilot.history[i].get('bind_cached')})")
        else:
            print(f"  {arch}: no result (payload exit codes {exitcodes})")
    failed = (not ok or any(sim.repo.result(t) is None for t in tids)
              or any(c != 0 for c in exitcodes))
    return 1 if failed else 0


def serve_fleet(arch: str, n_requests: int, n_pilots: int, *,
                slots: int = 2, max_len: int = 64, fail_at: int | None = None,
                fail_count: int = 1, lease_ttl: float = 0.5,
                registry=None, seed: int = 0, draft: str | None = None,
                spec_k: int = 4, robustness=None, chaos_plan=None,
                poison: int = 0, mesh_shape=None,
                trace: list[dict] | None = None) -> dict:
    """The fleet serve demo/driver: N pilots lease requests from one pool.

    ``fail_at`` hard-kills ``fail_count`` lease-holding pilots (one at
    ``fail_at`` settled requests, the next one ``fail_at`` later, ...) —
    the requeue-on-pilot-failure path.  ``draft`` turns on speculative
    decoding on every server: a draft arch name, or ``"self"`` for the
    self-draft ablation (the image's fixed draft seed keeps requeued
    requests replaying bitwise on survivors).

    Chaos drills: ``robustness`` (a
    :class:`~repro.serving.dispatch.RobustnessPolicy`) turns on the
    dispatcher's gray-failure hardening; ``chaos_plan`` (a
    :class:`~repro.core.chaos.FaultPlan`) runs a
    :class:`~repro.core.chaos.ChaosController` against the fleet for the
    duration of the trace; ``poison`` appends that many poison request
    entries (lethal while the plan arms them — each kills the pilot that
    fetches it until the pool quarantines it).

    Returns pool + timing stats; the caller owns no threads when this
    returns (fleet drained, pool closed).
    """
    from repro.core.chaos import ChaosController

    cfg = get_smoke_config(arch)
    sim = ClusterSim(registry=registry)
    pool = FleetDispatcher(lease_ttl=lease_ttl, policy=robustness)
    if trace is None:
        trace = make_trace(cfg.vocab_size, n_requests, max_len=max_len,
                           seed=seed)
    else:
        trace = list(trace)
    poison_rids = list(range(n_requests, n_requests + poison))
    for rid in poison_rids:
        trace.append({"rid": rid, "prompt": [1, 2, 3, 4],
                      "max_new_tokens": 4, "poison": True})
    fleet = sim.spawn_fleet(n_pilots, PilotConfig(max_payloads=2,
                                                  idle_grace=0.3))
    img = PayloadImage(arch=arch, shape="smoke", mode="serve",
                       draft=None if draft in (None, "self") else draft,
                       mesh_shape=(tuple(mesh_shape)
                                   if mesh_shape is not None else None))
    server_spec = {"slots": slots, "max_len": max_len}
    if mesh_shape is not None:
        # the fleet path plumbs the mesh through the startup spec too, so
        # telemetry/debug dumps of the spec show what geometry was served
        server_spec["mesh_shape"] = list(tuple(mesh_shape))
    if draft is not None:
        server_spec.update({"spec": "draft", "spec_k": spec_k})
    tids = fleet.submit_servers(img, pool.name, n=n_pilots,
                                spec=server_spec)
    # submit traffic only once the fleet is up and WARM, so TTFT measures
    # serving (queue wait + requeue delay), not server cold start
    if not pool.wait_servers(n_pilots, timeout=300.0):
        pool.close()
        fleet.drain_all()
        fleet.join_all(30.0)
        raise RuntimeError(
            f"only {len(pool.servers)}/{n_pilots} servers came up within "
            f"300s — refusing to serve traffic into a half-started fleet")
    ctl = (ChaosController(sim, fleet, pool=pool, plan=chaos_plan)
           if chaos_plan is not None else None)
    t0 = time.monotonic()
    if ctl is not None:
        ctl.start()            # t=0 for the plan's fault offsets
    pool.submit_trace(trace)
    pool.seal()                # the demo trace is the whole workload
    failed_pilots: list[str] = []
    try:
        for k in range(fail_count if fail_at else 0):
            if not pool.wait_completed(fail_at * (k + 1), timeout=300.0):
                break
            victim = _pick_victim(fleet, pool, exclude=failed_pilots)
            if victim is None:
                break
            failed_pilots.append(victim.pilot_id)
            sim.fail_node(victim.slice.slice_id)
        ok = pool.wait_all(timeout=600.0)
    finally:
        if ctl is not None:
            ctl.stop()
        pool.close()
        fleet.drain_all()
        fleet.join_all(30.0)
    wall = time.monotonic() - t0
    fleet.reap()
    stats = pool.stats()
    recs = pool.records()
    ttfts = [r.first_token_s for r in recs.values()
             if r.first_token_s is not None]
    goodput = sum(len(r.tokens) for r in recs.values()
                  if r.tokens is not None) / wall if wall else 0.0
    # same percentile definition as ServeEngine._stats, so fleet and
    # single-engine ttft_p*_s rows are directly comparable
    pct = lambda v, q: float(np.percentile(v, q)) if v else None
    # speculative effectiveness, averaged over the servers that ran with
    # spec on (their serve telemetry survives in the repo's task results)
    spec_rows = []
    for tid in tids:
        r = sim.repo.result(tid)
        if r and r.telemetry.get("serve", {}).get("spec") == "draft":
            spec_rows.append(r.telemetry["serve"])
    mean = lambda k: (sum(s[k] for s in spec_rows) / len(spec_rows)
                      if spec_rows else 0.0)
    # block-pool leak audit: every server that exited gracefully reports
    # its engine's residual allocation (killed servers can't — their KV
    # state died with the simulated node, which leaks nothing real)
    leaked = sum(r.telemetry["serve"]["fleet"].get("leaked_blocks", 0)
                 for r in (sim.repo.result(t) for t in tids)
                 if r and r.telemetry.get("serve", {}).get("fleet"))
    return {
        "drained": ok,
        "wall_s": wall,
        "goodput_tok_per_s": goodput,
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "failed_pilots": failed_pilots,
        "pilot_seconds": fleet.pilot_seconds(),
        "results": pool.results(),
        "spec_servers": len(spec_rows),
        "acceptance_rate": mean("acceptance_rate"),
        "tokens_per_step": mean("tokens_per_step"),
        "leaked_blocks": leaked,
        "poison_rids": poison_rids,
        "quarantined_rids": sorted(r.rid for r in recs.values()
                                   if r.quarantined),
        "fail_reasons": {r.rid: r.fail_reason for r in recs.values()
                         if r.failed},
        "chaos": ctl.stats() if ctl is not None else None,
        **stats,
    }


def serve_disagg(arch: str, n_requests: int, *, prefill_pilots: int = 2,
                 decode_pilots: int = 2, slots: int = 2, max_len: int = 64,
                 fail_prefill_at: int | None = None,
                 fail_decode_at: int | None = None, lease_ttl: float = 0.5,
                 registry=None, seed: int = 0,
                 trace: list[dict] | None = None) -> dict:
    """DISAGGREGATED fleet serve: prompts lease into a prefill pool whose
    engines export KV block handoffs; completed prefills become decode-pool
    leases (the :class:`~repro.serving.dispatch.DisaggRouter` forward) and
    a separate decode fleet resumes each stream from its handoff.

    ``fail_prefill_at`` / ``fail_decode_at`` hard-kill a lease-holding
    pilot of the respective stage after K settled requests in that stage —
    a dead prefill pilot's prompts replay from the PROMPT on survivors; a
    dead decode pilot's streams replay from the HANDOFF (the prompt is
    never re-prefilled).  Params come from the image seed on every server,
    so either replay reproduces the lost tokens bitwise.
    """
    from repro.serving.dispatch import DisaggRouter

    cfg = get_smoke_config(arch)
    sim = ClusterSim(registry=registry)
    router = DisaggRouter(lease_ttl=lease_ttl)
    if trace is None:
        trace = make_trace(cfg.vocab_size, n_requests, max_len=max_len,
                           seed=seed)
    pf_fleet = sim.spawn_fleet(prefill_pilots,
                               PilotConfig(max_payloads=2, idle_grace=0.3))
    dc_fleet = sim.spawn_fleet(decode_pilots,
                               PilotConfig(max_payloads=2, idle_grace=0.3))
    # role is part of the image key: the prefill image never compiles the
    # decode step; the decode image never compiles the admission prefills
    pf_img = PayloadImage(arch=arch, shape="smoke", mode="serve",
                          role="prefill")
    dc_img = PayloadImage(arch=arch, shape="smoke", mode="serve",
                          role="decode")
    pf_spec = {"slots": slots, "max_len": max_len,
               "server_labels": {"pool": "prefill"}}
    dc_spec = {"slots": slots, "max_len": max_len,
               "server_labels": {"pool": "decode"}}
    pf_tids = pf_fleet.submit_servers(pf_img, router.prefill.name,
                                      n=prefill_pilots, spec=pf_spec)
    dc_tids = dc_fleet.submit_servers(dc_img, router.decode.name,
                                      n=decode_pilots, spec=dc_spec)
    for pool, n in ((router.prefill, prefill_pilots),
                    (router.decode, decode_pilots)):
        if not pool.wait_servers(n, timeout=300.0):
            router.close()
            for f in (pf_fleet, dc_fleet):
                f.drain_all()
                f.join_all(30.0)
            raise RuntimeError(
                f"only {len(pool.servers)}/{n} {pool.name} servers came "
                f"up within 300s")
    t0 = time.monotonic()
    router.submit_trace(trace)
    router.seal()
    failed = {"prefill": [], "decode": []}
    try:
        for stage, pool, fleet, at in (
                ("prefill", router.prefill, pf_fleet, fail_prefill_at),
                ("decode", router.decode, dc_fleet, fail_decode_at)):
            if at is None:
                continue
            if not pool.wait_completed(at, timeout=300.0):
                continue
            victim = _pick_victim(fleet, pool)
            if victim is not None:
                failed[stage].append(victim.pilot_id)
                sim.fail_node(victim.slice.slice_id)
        ok = router.wait_all(timeout=600.0)
    finally:
        router.close()
        for f in (pf_fleet, dc_fleet):
            f.drain_all()
            f.join_all(30.0)
    wall = time.monotonic() - t0
    pf_fleet.reap()
    dc_fleet.reap()
    # end-to-end TTFT: the FIRST generated token exists at prefill export
    # (it rides the handoff), so the prefill-stage records — whose
    # first_token_s is measured against the ORIGINAL submit time — are the
    # honest time-to-first-token.  The decode-stage records measure the
    # same zero but include the decode pool's import queue: that is the
    # resume latency (time until the stream starts advancing again).
    recs = router.decode.records()
    ttfts = [r.first_token_s for r in router.prefill.records().values()
             if r.first_token_s is not None]
    resumes = [r.first_token_s for r in recs.values()
               if r.first_token_s is not None]
    pct = lambda v, q: float(np.percentile(v, q)) if v else None
    goodput = sum(len(r.tokens) for r in recs.values()
                  if r.tokens is not None) / wall if wall else 0.0
    leaked = exported = imported = 0
    for tid in pf_tids + dc_tids:
        r = sim.repo.result(tid)
        sv = r.telemetry.get("serve", {}) if r else {}
        if sv.get("fleet"):
            leaked += sv["fleet"].get("leaked_blocks", 0)
        exported += sv.get("prefills_exported", 0) or 0
        imported += sv.get("handoffs_imported", 0) or 0
    return {
        "drained": ok,
        "wall_s": wall,
        "goodput_tok_per_s": goodput,
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "resume_p50_s": pct(resumes, 50),
        "resume_p99_s": pct(resumes, 99),
        "failed_pilots": failed,
        "pilot_seconds": (pf_fleet.pilot_seconds()
                          + dc_fleet.pilot_seconds()),
        "results": router.results(),
        "leaked_blocks": leaked,
        "prefills_exported": exported,
        "handoffs_imported": imported,
        "pool_pressure": router.pool_pressure(),
        "stats": router.stats(),
    }


def serve_disagg_schedule(arch: str, schedule: list[tuple[float, dict]], *,
                          slots: int = 2, max_len: int = 64,
                          prefill_policy=None, decode_policy=None,
                          initial_pilots: int = 1, lease_ttl: float = 0.5,
                          idle_grace: float = 0.5, registry=None) -> dict:
    """Disaggregated fleets under TWO independent autoscalers, one per
    role pool, each reading its own label's ``pool_pressure()`` slice —
    the demand-shaped heterogeneous-pool loop: a prefill-bound trace grows
    only the prefill fleet, a decode-bound trace only the decode fleet."""
    from repro.core.autoscaler import FleetAutoscaler
    from repro.serving.dispatch import DisaggRouter

    sim = ClusterSim(registry=registry)
    router = DisaggRouter(lease_ttl=lease_ttl)
    pf_img = PayloadImage(arch=arch, shape="smoke", mode="serve",
                          role="prefill")
    dc_img = PayloadImage(arch=arch, shape="smoke", mode="serve",
                          role="decode")
    pf_spec = {"slots": slots, "max_len": max_len,
               "server_labels": {"pool": "prefill"}}
    dc_spec = {"slots": slots, "max_len": max_len,
               "server_labels": {"pool": "decode"}}
    pf_fleet = sim.spawn_fleet(initial_pilots,
                               PilotConfig(max_payloads=4,
                                           idle_grace=idle_grace))
    dc_fleet = sim.spawn_fleet(initial_pilots,
                               PilotConfig(max_payloads=4,
                                           idle_grace=idle_grace))
    scalers = []
    out: dict = {}
    try:
        if initial_pilots:
            pf_fleet.submit_servers(pf_img, router.prefill.name,
                                    n=initial_pilots, spec=pf_spec)
            dc_fleet.submit_servers(dc_img, router.decode.name,
                                    n=initial_pilots, spec=dc_spec)
            for pool in (router.prefill, router.decode):
                if not pool.wait_servers(initial_pilots, timeout=300.0):
                    raise RuntimeError(f"{pool.name} servers not warm "
                                       f"within 300s")
        for fleet, img, pool, label, policy, spec in (
                (pf_fleet, pf_img, router.prefill, "prefill",
                 prefill_policy, pf_spec),
                (dc_fleet, dc_img, router.decode, "decode",
                 decode_policy, dc_spec)):
            if policy is None:
                continue
            sc = FleetAutoscaler(fleet, img, pool=pool, pool_label=label,
                                 policy=policy, spec=spec)
            sc.start()
            scalers.append((label, sc))
        t0 = time.monotonic()
        for dt, entry in schedule:
            lag = dt - (time.monotonic() - t0)
            if lag > 0:
                time.sleep(lag)
            router.submit(entry)
        router.seal()
        out["drained"] = router.wait_all(timeout=600.0)
        out["wall_s"] = time.monotonic() - t0
    finally:
        for _, sc in scalers:
            sc.stop()
        router.close()
        for f in (pf_fleet, dc_fleet):
            f.drain_all()
            f.join_all(30.0)
            f.reap()
    recs = router.decode.records()
    ttfts = [r.first_token_s for r in recs.values()
             if r.first_token_s is not None]
    pct = lambda v, q: float(np.percentile(v, q)) if v else None
    out.update({
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "pilot_seconds": {"prefill": pf_fleet.pilot_seconds(),
                          "decode": dc_fleet.pilot_seconds()},
        "peak_pilots": {"prefill": None, "decode": None},
        "results": router.results(),
        "stats": router.stats(),
    })
    for label, sc in scalers:
        out.setdefault("autoscale", {})[label] = sc.stats()
        out["peak_pilots"][label] = sc.peak_live
    return out


def make_bursty_schedule(trace: list[dict], *, bursts: int, burst_s: float,
                         gap_s: float, seed: int = 0) -> list[tuple[float, dict]]:
    """Square-wave arrival schedule with Poisson arrivals inside each high
    phase: the trace is split evenly across ``bursts`` bursts; within a
    burst, inter-arrival gaps are exponential (rate = burst size /
    burst_s, clipped to the burst window), and between bursts the pool
    goes quiet for ``gap_s`` — the demand shape an autoscaler must track
    without flapping."""
    rng = np.random.default_rng(seed)
    per = (len(trace) + bursts - 1) // bursts
    out: list[tuple[float, dict]] = []
    for b in range(bursts):
        chunk = trace[b * per:(b + 1) * per]
        if not chunk:
            break
        t = b * (burst_s + gap_s)
        rate = len(chunk) / burst_s
        offs = np.minimum(np.cumsum(rng.exponential(1.0 / rate,
                                                    size=len(chunk))),
                          burst_s)
        for off, e in zip(offs, chunk):
            out.append((t + float(off), e))
    return out


def serve_fleet_schedule(arch: str, schedule: list[tuple[float, dict]], *,
                         slots: int = 2, max_len: int = 64,
                         policy=None, n_pilots: int | None = None,
                         initial_pilots: int = 1, lease_ttl: float = 0.5,
                         idle_grace: float = 0.5, registry=None,
                         settle_to_zero: bool = True) -> dict:
    """Drive a serving fleet through a WALL-CLOCK arrival schedule
    (``[(t_offset_s, entry), ...]``, sorted by offset).

    ``policy`` (an :class:`~repro.core.autoscaler.AutoscalePolicy`) runs
    the fleet under the demand-driven autoscaler starting from
    ``initial_pilots``; ``policy=None`` runs a STATIC fleet of
    ``n_pilots`` — the peak-sized baseline the autoscaler is judged
    against.  Returns pool stats + pool-level TTFT percentiles +
    ``pilot_seconds`` (fleet-lifetime slice holding, the cost metric) and,
    when autoscaled, the decision ledger / flap count / scale-to-zero
    outcome."""
    from repro.core.autoscaler import FleetAutoscaler

    sim = ClusterSim(registry=registry)
    pool = FleetDispatcher(lease_ttl=lease_ttl)
    img = PayloadImage(arch=arch, shape="smoke", mode="serve")
    spec = {"slots": slots, "max_len": max_len}
    n_start = n_pilots if policy is None else max(policy.min_pilots,
                                                 initial_pilots)
    if policy is None and n_pilots is None:
        raise ValueError("static mode needs n_pilots")
    fleet = sim.spawn_fleet(n_start, PilotConfig(max_payloads=4,
                                                 idle_grace=idle_grace))
    scaler = None
    out: dict = {}
    try:
        if n_start:
            fleet.submit_servers(img, pool.name, n=n_start, spec=spec)
            if not pool.wait_servers(n_start, timeout=300.0):
                raise RuntimeError(
                    f"only {len(pool.servers)}/{n_start} servers warm "
                    f"within 300s")
        if policy is not None:
            scaler = FleetAutoscaler(fleet, img, pool=pool, policy=policy,
                                     spec=spec)
            scaler.start()
        t0 = time.monotonic()
        for dt, entry in schedule:
            lag = dt - (time.monotonic() - t0)
            if lag > 0:
                time.sleep(lag)
            pool.submit(entry)
        pool.seal()
        ok = pool.wait_all(timeout=600.0)
        wall = time.monotonic() - t0
        out["drained"] = ok
        out["wall_s"] = wall
        if scaler is not None and policy.min_pilots == 0 and settle_to_zero:
            # the empty-trace epilogue: demand is 0, so the loop must shed
            # every pilot (victims exit via drain/idle_grace) — the
            # scale-to-zero half of the (g)->(h) lifecycle
            budget = (policy.down_cooldown
                      + policy.down_stable_ticks * policy.interval + 30.0)
            deadline = time.monotonic() + budget
            while fleet.size() > 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            out["scaled_to_zero"] = fleet.size() == 0
            out["scale_to_zero_s"] = time.monotonic() - t0 - wall
    finally:
        if scaler is not None:
            scaler.stop()
        pool.close()
        fleet.drain_all()
        fleet.join_all(30.0)
        fleet.reap()
    recs = pool.records()
    ttfts = [r.first_token_s for r in recs.values()
             if r.first_token_s is not None]
    pct = lambda v, q: float(np.percentile(v, q)) if v else None
    out.update({
        "ttft_p50_s": pct(ttfts, 50),
        "ttft_p99_s": pct(ttfts, 99),
        "pilot_seconds": fleet.pilot_seconds(),
        "results": pool.results(),
        **pool.stats(),
    })
    if scaler is not None:
        out["autoscale"] = scaler.stats()
        out["decisions"] = [dataclasses.asdict(d) for d in scaler.decisions]
        out["t_start"] = t0
    return out


def _pick_victim(fleet, pool, *, exclude=()):
    """The live pilot holding the most request leases (never a survivor of
    a previous kill round that holds none — killing an idle pilot exercises
    nothing)."""
    holders = pool.lease_holders()
    best, best_n = None, -1
    for p in fleet.live():
        if p.pilot_id in exclude:
            continue
        n = len(holders.get(p.pilot_id, []))
        if n > best_n:
            best, best_n = p, n
    return best if best_n > 0 else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--archs", default=None,
                    help="comma list for --via-pilots multi-model demo")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=None,
                    help="engine slots (default: 4 direct; image shape "
                         "via pilots)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="engine KV length (default: 128 direct; image "
                         "shape via pilots)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--wave", action="store_true",
                    help="static wave-batching baseline (for comparison)")
    ap.add_argument("--kv", choices=("paged", "dense"), default=None,
                    help="KV layout (default: paged for decoder LMs; "
                         "dense is the ablation)")
    ap.add_argument("--prefill", choices=("oneshot", "chunked"),
                    default="oneshot",
                    help="admission prefill: whole-bucket, or chunks "
                         "interleaved with decode")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size (default: dense-equivalent)")
    ap.add_argument("--dup-rate", type=float, default=0.0,
                    help="fraction of repeated prompts (prefix-cache hits)")
    ap.add_argument("--draft", default=None,
                    help="speculative decoding: draft model arch, or "
                         "'self' for the self-draft ablation (direct and "
                         "fleet modes)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative step")
    ap.add_argument("--mesh", default=None,
                    help="serve over a device mesh, 'AxB' = (data, model) "
                         "— e.g. '1x2' shards params + paged KV pools on "
                         "the head axis over 2 devices (direct and fleet "
                         "modes)")
    ap.add_argument("--via-pilots", action="store_true")
    ap.add_argument("--pilots", type=int, default=None,
                    help="fleet serve: N pilots lease requests from one "
                         "shared pool")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="fleet serve: hard-kill a lease-holding pilot "
                         "after K completed requests")
    ap.add_argument("--chaos", action="store_true",
                    help="fleet serve: run the canned chaos drill (crash + "
                         "stall + slow + flaky heartbeat + one poison "
                         "request) with gray-failure hardening on")
    ap.add_argument("--hedge", type=float, default=None,
                    help="fleet serve: enable hedged re-dispatch with this "
                         "straggler budget factor (x pool p95 service time)")
    ap.add_argument("--quarantine-after", type=int, default=None,
                    help="fleet serve: quarantine a request once this many "
                         "distinct pilots died holding it (0 disables)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serve: a prefill fleet exports KV "
                         "handoffs that a decode fleet resumes (pool sizes "
                         "via --prefill-pilots/--decode-pilots)")
    ap.add_argument("--prefill-pilots", type=int, default=2,
                    help="disagg: prefill pool size")
    ap.add_argument("--decode-pilots", type=int, default=2,
                    help="disagg: decode pool size")
    ap.add_argument("--fail-prefill-at", type=int, default=None,
                    help="disagg: kill a prefill pilot after K settled "
                         "prefills (replay-from-prompt)")
    ap.add_argument("--fail-decode-at", type=int, default=None,
                    help="disagg: kill a decode pilot after K finished "
                         "streams (replay-from-handoff)")
    ap.add_argument("--autoscale", action="store_true",
                    help="fleet serve on a bursty square-wave trace with "
                         "the demand-driven autoscaler (--pilots caps the "
                         "fleet; starts at 1, scales to zero in the gaps)")
    args = ap.parse_args()
    enable_compile_cache()

    mesh_shape = None
    if args.mesh:
        from repro.runtime.mesh import parse_mesh_shape
        mesh_shape = parse_mesh_shape(args.mesh)

    if args.disagg:
        out = serve_disagg(args.arch, args.requests,
                           prefill_pilots=args.prefill_pilots,
                           decode_pilots=args.decode_pilots,
                           slots=args.slots or 2,
                           max_len=args.max_len or 64,
                           fail_prefill_at=args.fail_prefill_at,
                           fail_decode_at=args.fail_decode_at)
        out.pop("results")
        out.pop("pool_pressure", None)
        print(json.dumps(out, indent=1))
        return
    if args.autoscale:
        from repro.core.autoscaler import AutoscalePolicy
        cfg = get_smoke_config(args.arch)
        max_len = args.max_len or 64
        slots = args.slots or 2
        n_peak = args.pilots or 4
        trace = make_trace(cfg.vocab_size, args.requests, max_len=max_len)
        schedule = make_bursty_schedule(trace, bursts=3, burst_s=1.0,
                                        gap_s=5.0)
        out = serve_fleet_schedule(
            args.arch, schedule, slots=slots, max_len=max_len,
            policy=AutoscalePolicy(min_pilots=0, max_pilots=n_peak,
                                   slots_per_pilot=slots))
        out.pop("results")
        out.pop("t_start", None)
        print(json.dumps(out, indent=1))
        return
    if args.pilots:
        robustness, chaos_plan, poison = None, None, 0
        if args.chaos or args.hedge is not None \
                or args.quarantine_after is not None:
            from repro.serving.dispatch import RobustnessPolicy
            robustness = RobustnessPolicy()
            if args.hedge is not None:
                robustness.hedge_factor = args.hedge
            if args.quarantine_after is not None:
                robustness.quarantine_after = args.quarantine_after
        if args.chaos:
            from repro.core.chaos import FaultPlan, FaultSpec
            chaos_plan = FaultPlan(faults=[
                FaultSpec(kind="crash", at_s=0.5),
                FaultSpec(kind="stall", at_s=1.0, duration_s=2.0),
                FaultSpec(kind="slow", at_s=1.5, duration_s=2.0, factor=5.0),
                FaultSpec(kind="flaky_heartbeat", at_s=1.5, duration_s=2.0),
            ], poison=True)
            poison = 1
        out = serve_fleet(args.arch, args.requests, args.pilots,
                          slots=args.slots or 2, max_len=args.max_len or 64,
                          fail_at=args.fail_at, draft=args.draft,
                          spec_k=args.spec_k, robustness=robustness,
                          chaos_plan=chaos_plan, poison=poison,
                          mesh_shape=mesh_shape)
        out.pop("results")
        if mesh_shape is not None:
            print(f"[mesh] shape={'x'.join(map(str, mesh_shape))} "
                  f"(fleet: every server shards over its own mesh)")
        if args.draft:
            print(f"[spec] servers={out['spec_servers']} "
                  f"acceptance_rate={out['acceptance_rate']:.3f} "
                  f"tokens_per_step={out['tokens_per_step']:.2f}")
        print(json.dumps(out, indent=1))
        return
    if args.via_pilots:
        archs = (args.archs or f"{args.arch},gemma-2b").split(",")
        sys.exit(serve_via_pilots(archs, n_requests=args.requests,
                                  slots=args.slots, max_len=args.max_len))
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    draft_cfg = None
    if args.draft and args.draft != "self":
        draft_cfg = (get_smoke_config(args.draft) if args.smoke
                     else get_config(args.draft))
    stats = serve_direct(cfg, args.requests, args.slots or 4,
                         args.max_len or 128,
                         admission="wave" if args.wave else "continuous",
                         kv=args.kv, prefill=args.prefill,
                         num_blocks=args.num_blocks,
                         dup_rate=args.dup_rate,
                         spec="draft" if args.draft else "off",
                         spec_k=args.spec_k, draft_cfg=draft_cfg,
                         mesh_shape=mesh_shape)
    if mesh_shape is not None:
        print(f"[mesh] shape={'x'.join(map(str, mesh_shape))} "
              f"devices={stats['mesh_devices']} "
              f"kv_pool_bytes_per_device={stats['kv_pool_bytes_per_device']} "
              f"(total {stats['kv_pool_bytes']})")
    if args.draft:
        print(f"[spec] spec={stats['spec']} "
              f"acceptance_rate={stats['acceptance_rate']:.3f} "
              f"tokens_per_step={stats['tokens_per_step']:.2f} "
              f"draft_overhead_s={stats['draft_overhead_s']:.3f}")
    print(json.dumps(stats, indent=1))


if __name__ == "__main__":
    main()
