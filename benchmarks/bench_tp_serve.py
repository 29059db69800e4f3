"""Tensor-parallel sharded serving benchmark (paper §late-binding over
held multi-device slices).

A mesh-bound serve payload late-binds one SPMD engine over the devices
its pilot already holds: paged KV pools shard on the head (GQA) /
latent (MLA) dim over the "model" axis, Pallas paged-attention runs
under ``shard_map``, and the packed per-step device->host transfer
stays exactly ONE fully-replicated array — so continuous batching,
prefix COW and speculative decode work unchanged on top.

The serve-TP rules are ORDER-PRESERVING (column-parallel params only;
every cross-shard contraction gathers first): the sharded engine's
token streams are bitwise identical to the single-device engine's, and
the bench RAISES on any divergence, on a broken one-transfer invariant,
and on a per-device KV-pool footprint above 0.6x the single-device
pool on a 2-way mesh.

Needs >1 device, and XLA's forced host-device count must be set before
jax imports — so the measured section self-spawns as a child process
(``--child``) with ``--xla_force_host_platform_device_count=2`` and
``JAX_PLATFORMS=cpu`` — a rehearsal on host devices that never contends
for an accelerator the parent may hold; the parent stays device-count
agnostic and just gates the child's JSON.

  smoke: GQA (Pallas paged attention) only, short trace — the CI gate.
  full:  GQA + MLA + GQA-with-speculation, longer trace; records tok/s
         sharded vs single and per-device KV bytes for each.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# child: the only process that sees >1 device
# ---------------------------------------------------------------------------

def _child(mode: str) -> None:
    import dataclasses
    import time

    import jax

    import repro.configs.base as b
    from repro.launch.serve import make_trace
    from repro.models.api import build_model
    from repro.runtime.mesh import serve_mesh
    from repro.serving.engine import ServeEngine

    n_req = 6 if mode == "smoke" else 16
    max_len = 64 if mode == "smoke" else 96
    cases = [("gqa", "starcoder2-3b", {"attn_impl": "pallas"}, {})]
    if mode == "full":
        cases += [("mla", "minicpm3-4b", {}, {}),
                  ("gqa_spec", "starcoder2-3b", {"attn_impl": "pallas"},
                   {"spec": "draft", "spec_k": 3})]

    def run(cfg, mesh, **kw):
        params = build_model(cfg).init(jax.random.key(0))
        eng = ServeEngine(cfg, params, slots=2, max_len=max_len,
                          mesh=mesh, **kw)
        trace = make_trace(cfg.vocab_size, n_req, max_len=max_len,
                           seed=0, dup_rate=0.3)
        t0 = time.monotonic()
        eng.run_trace(trace)
        wall = time.monotonic() - t0
        toks = {r.rid: list(r.tokens) for r in eng.done.values()}
        return eng, toks, sum(len(t) for t in toks.values()) / wall

    out = {"devices": jax.device_count()}
    mesh = serve_mesh((1, 2))
    for name, arch, flags, kw in cases:
        cfg = b.get_smoke_config(arch)
        if flags:
            cfg = dataclasses.replace(cfg, **flags)
        e1, t1, tps1 = run(cfg, None, **kw)
        e2, t2, tps2 = run(cfg, mesh, **kw)
        kvb = e2.kv_pool_bytes()
        out[name] = {
            "parity": t1 == t2,
            "d2h_per_step": e2.d2h_transfers / max(1, e2.steps),
            "kv_bytes_single": e1.kv_pool_bytes()["kv_pool_bytes_per_device"],
            "kv_bytes_per_device": kvb["kv_pool_bytes_per_device"],
            "kv_ratio": (kvb["kv_pool_bytes_per_device"]
                         / kvb["kv_pool_bytes"]),
            "tok_s_single": tps1,
            "tok_s_sharded": tps2,
        }
    json.dump(out, sys.stdout)


# ---------------------------------------------------------------------------
# parent: spawn, gate, report
# ---------------------------------------------------------------------------

def _spawn(mode: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + str(REPO)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2")
    env["JAX_PLATFORMS"] = "cpu"      # host devices; never the chip
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_tp_serve", "--child", mode],
        capture_output=True, text=True, timeout=3600, env=env,
        cwd=str(REPO))
    if r.returncode != 0:
        raise RuntimeError(f"tp_serve child failed: {r.stderr[-2000:]}")
    return json.loads(r.stdout)


def _gate(rec: dict, name: str) -> None:
    if not rec["parity"]:
        raise AssertionError(f"{name}: sharded tokens != single-device")
    if rec["d2h_per_step"] != 1.0:
        raise AssertionError(
            f"{name}: one-transfer invariant broken ({rec['d2h_per_step']})")
    if rec["kv_ratio"] > 0.6:
        raise AssertionError(
            f"{name}: per-device KV pool {rec['kv_ratio']:.2f}x > 0.6x")


def _rows(out: dict, cases) -> list:
    rows = []
    for name in cases:
        rec = out[name]
        _gate(rec, name)
        rows += [
            (f"tp_{name}_bitwise_parity", 1.0,
             "sharded == single-device token streams"),
            (f"tp_{name}_d2h_per_step", rec["d2h_per_step"],
             "packed transfers per decode step (must be 1)"),
            (f"tp_{name}_kv_bytes_per_device", rec["kv_bytes_per_device"],
             f"vs {rec['kv_bytes_single']} single-device"),
            (f"tp_{name}_kv_ratio", rec["kv_ratio"],
             "per-device / total pool bytes on 1x2 mesh"),
            (f"tp_{name}_tok_s_sharded", rec["tok_s_sharded"],
             f"single-device {rec['tok_s_single']:.1f} tok/s"),
        ]
    return rows


def run_smoke():
    """CI gate: bitwise parity + one-transfer + sharded pools on a 1x2
    host mesh, GQA via the Pallas paged-attention kernel under
    shard_map."""
    return _rows(_spawn("smoke"), ["gqa"])


def run():
    """Full battery: GQA, MLA and GQA+speculative-decode, longer trace."""
    return _rows(_spawn("full"), ["gqa", "mla", "gqa_spec"])


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--child":
        _child(sys.argv[2])
    else:
        for row in (run_smoke() if "--smoke" in sys.argv else run()):
            print(row)
