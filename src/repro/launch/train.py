"""End-to-end training driver THROUGH the pilot system.

The canonical production invocation (paper lifecycle a-h, late binding,
checkpoint/restart, monitoring) on synthetic data:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --steps 300 \
      --batch 8 --seq 512 --ckpt /tmp/ck [--smoke] [--direct]

``--direct`` bypasses the pilot system for a plain jit loop (useful for
debugging / perf A-B).  With ``--fail-at N`` a simulated node failure kills
the first pilot mid-run; the lease expires, a replacement pilot picks the
task up and resumes from the last checkpoint — the fault-tolerance demo.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from repro.configs.base import get_config, get_smoke_config
from repro.core.cluster import ClusterSim
from repro.core.images import PayloadImage
from repro.core.pilot import PilotConfig
from repro.core.taskrepo import TaskRepo
from repro.data.synthetic import SyntheticConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import init_train_state, make_train_step
from repro.optim.adamw import OptimConfig


def train_direct(cfg, steps: int, batch: int, seq: int, *, log_every=10):
    import jax.numpy as jnp
    step_fn = jax.jit(make_train_step(cfg, OptimConfig(
        total_steps=steps, warmup_steps=max(steps // 20, 5))),
        donate_argnums=0)
    state = init_train_state(cfg, jax.random.key(0))
    data = SyntheticLM(SyntheticConfig(cfg.vocab_size, seq, batch))
    losses = []
    t0 = time.monotonic()
    for i in range(steps):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state, metrics = step_fn(state, b)
        loss = float(metrics["loss"])
        losses.append(loss)
        if i % log_every == 0 or i == steps - 1:
            dt = (time.monotonic() - t0) / (i + 1)
            print(f"step {i:4d}  loss {loss:.4f}  ({dt*1e3:.0f} ms/step)")
    return losses


def train_via_pilots(arch: str, smoke: bool, steps: int, *, ckpt: str | None,
                     fail_at: float | None, n_pilots: int = 1,
                     seq: int = 64, batch: int = 2):
    repo = TaskRepo(lease_ttl=5.0)
    sim = ClusterSim(repo=repo)
    resume = {"ckpt_dir": ckpt, "ckpt_every": max(steps // 10, 1)} if ckpt else {}
    tid = repo.submit(
        PayloadImage(arch=arch, shape=f"custom:{seq}x{batch}", mode="train",
                     smoke=smoke),
        n_steps=steps, max_wall=3600.0, resume=resume)
    slices = sim.provision(n_pilots)
    pilots = [sim.spawn_pilot(s, PilotConfig(max_payloads=4, idle_grace=3.0))
              for s in slices]
    if fail_at is not None:
        time.sleep(fail_at)
        print(f"[train] injecting node failure on pilot {pilots[0].pilot_id}")
        sim.fail_node(slices[0].slice_id)
        # a replacement pilot takes over after the lease expires
        (s2,) = sim.provision(1)
        pilots.append(sim.spawn_pilot(s2, PilotConfig(max_payloads=4,
                                                      idle_grace=6.0)))
    ok = sim.run_until_drained(timeout=3600.0)
    sim.join_all(timeout=30.0)
    res = repo.result(tid)
    print(f"[train] drained={ok} repo={repo.stats()}")
    if res is not None:
        t = res.telemetry
        print(json.dumps({
            "task": tid, "pilot": res.pilot_id, "exit": res.exitcode,
            "steps": t.get("steps"), "resumed_from": t.get("resumed_from"),
            "first_loss": t.get("first_loss"), "last_loss": t.get("last_loss"),
        }, indent=1))
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--direct", action="store_true",
                    help="plain jit loop, no pilot system")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--fail-at", type=float, default=None,
                    help="seconds until a simulated node failure")
    ap.add_argument("--pilots", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    if args.direct:
        cfg = (get_smoke_config(args.arch) if args.smoke
               else get_config(args.arch))
        losses = train_direct(cfg, args.steps, args.batch, args.seq)
        print(f"[train] first={losses[0]:.4f} last={losses[-1]:.4f}")
    else:
        train_via_pilots(args.arch, args.smoke, args.steps,
                         ckpt=args.ckpt, fail_at=args.fail_at,
                         n_pilots=args.pilots, seq=args.seq,
                         batch=args.batch)


if __name__ == "__main__":
    main()
