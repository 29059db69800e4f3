"""Tensor-parallel (SPMD) serving.

Host-side fast tests: mesh-shape parsing, the serve image's mesh-aware
registry key (per-(image, mesh) single-flight), and the capacity
accounting rule that a mesh-bound server is ONE unit of slot capacity.

The device-level battery — bitwise sharded-vs-single-device token parity
for GQA (Pallas paged attention under shard_map) and MLA, the
one-transfer-per-step invariant, per-device KV pool bytes, and COW/
refcount balance on sharded pools — needs more than one device, so it
runs in a subprocess with ``--xla_force_host_platform_device_count=2``
(XLA flags must be set before jax imports; same pattern as
test_dryrun.py).
"""

import dataclasses
import json
import pathlib
import subprocess
import sys
import threading

import pytest

from repro.core.autoscaler import AutoscalePolicy, FleetAutoscaler
from repro.core.images import Executable, ExecutableRegistry, PayloadImage
from repro.runtime.mesh import parse_mesh_shape, serve_mesh

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# --mesh AxB parsing
# ---------------------------------------------------------------------------

def test_parse_mesh_shape():
    assert parse_mesh_shape("1x2") == (1, 2)
    assert parse_mesh_shape("2x4") == (2, 4)
    assert parse_mesh_shape("4") == (1, 4)      # bare device count
    for bad in ("2x3x4", "ax2", "0x2", ""):
        with pytest.raises(ValueError):
            parse_mesh_shape(bad)


# ---------------------------------------------------------------------------
# serve image key + registry: compiles are per (image, mesh)
# ---------------------------------------------------------------------------

def _img(**kw):
    return PayloadImage(arch="smollm-360m", shape="smoke", mode="serve",
                        smoke=True, **kw)


def test_payload_image_key_includes_mesh_shape():
    assert _img().key() != _img(mesh_shape=(1, 2)).key()
    assert _img(mesh_shape=(1, 2)).key() != _img(mesh_shape=(2, 1)).key()


def test_registry_key_distinguishes_mesh():
    img = _img()
    k_none = ExecutableRegistry._key(img, None)
    k_mesh = ExecutableRegistry._key(img, serve_mesh((1, 1)))
    assert k_none != k_mesh


def test_registry_prefetch_single_flight_per_image_mesh(monkeypatch):
    """Two prefetches of the same (image, mesh) join one worker; a
    different mesh for the same image is a different compile."""
    reg = ExecutableRegistry()
    gate = threading.Event()
    keys = []

    def fake_pull(image, mesh=None):
        keys.append(ExecutableRegistry._key(image, mesh))
        gate.wait(10)
        return Executable(image=image, fn=None, make_inputs=None,
                          compile_seconds=0.0)

    monkeypatch.setattr(reg, "pull", fake_pull)
    img = _img()
    mesh = serve_mesh((1, 1))
    e1 = reg.prefetch(img, mesh)
    e2 = reg.prefetch(img, mesh)        # joins the in-flight prefetch
    e3 = reg.prefetch(img, None)        # distinct key -> its own worker
    assert e1 is e2
    gate.set()
    assert e1.wait(10) and e3.wait(10)
    assert reg.stats["prefetches"] == 2
    assert len(set(keys)) == 2


# ---------------------------------------------------------------------------
# capacity accounting: a mesh-bound server is ONE capacity unit
# ---------------------------------------------------------------------------

class _StubFleet:
    def __init__(self, n: int = 0):
        self.n = n
        self.draining_n = 0

    def size(self):
        return self.n

    def draining(self):
        return self.draining_n

    def scale_up(self, n):
        self.n += n
        return [object()] * n

    def scale_down(self, n):
        self.n -= n
        return []


def test_autoscaler_mesh_server_is_one_capacity_unit():
    """demand 8 against 2-slot sharded servers needs 4 servers — the 4
    devices backing each server must never multiply into capacity."""
    sig = {"demand": 8, "pool_slots_per_server": 2.0,
           "pool_mesh_devices": 4}
    fleet = _StubFleet(0)
    sc = FleetAutoscaler(fleet, None,
                         policy=AutoscalePolicy(slots_per_pilot=1),
                         signals_fn=lambda: dict(sig),
                         clock=lambda: 1000.0)
    sc.tick()
    assert fleet.size() == 4, fleet.size()


def test_pool_pressure_reports_per_server_slots_and_mesh():
    from repro.serving.dispatch import FleetDispatcher
    pool = FleetDispatcher(name="tp-test")
    for sid, slots in (("s1", 2), ("s2", 4)):
        pool.announce(sid)
        pool.report_telemetry(sid, {"slots": slots, "mesh_devices": 2,
                                    "kv_memory_utilization": 0.1})
    pp = pool.pool_pressure()
    assert pp["slots_per_server"] == pytest.approx(3.0)
    assert pp["mesh_devices"] == 2


# ---------------------------------------------------------------------------
# device battery (2 forced host devices, subprocess)
# ---------------------------------------------------------------------------

_BATTERY = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
import dataclasses, json, sys
import jax
import jax.numpy as jnp
import repro.configs.base as b
from repro.launch.serve import make_trace
from repro.models.api import build_model, init_decode_state
from repro.runtime.mesh import MODEL_AXIS, serve_mesh
from repro.runtime.sharding import serve_param_shardings, serve_state_shardings
from repro.serving.engine import ServeEngine

assert jax.device_count() == 2
mesh = serve_mesh((1, 2))
out = {}

def run(cfg, mesh, **kw):
    import numpy as np
    from repro.serving.engine import Request
    params = build_model(cfg).init(jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, mesh=mesh, **kw)
    trace = make_trace(cfg.vocab_size, 6, max_len=64, seed=0, dup_rate=0.3)
    eng.run_trace(trace)
    toks = {r.rid: list(r.tokens) for r in eng.done.values()}
    # churn with a long shared prompt (several FULL blocks): admissions
    # map prefix blocks copy-free with refcount bumps, evictions return
    # them — COW/refcount balance on the SHARDED pools is the invariant
    base = (np.arange(40) % (cfg.vocab_size - 2) + 2).astype(np.int32)
    for i in range(6):
        eng.submit(Request(rid=1000 + i, prompt=base.copy(),
                           max_new_tokens=4))
    eng.run()
    toks.update({r.rid: list(r.tokens) for r in eng.done.values()})
    return eng, toks

for name, arch, flags, kw in [
        ("gqa", "starcoder2-3b", {"attn_impl": "pallas"}, {}),
        ("gqa_spec", "starcoder2-3b", {"attn_impl": "pallas"},
         {"spec": "draft", "spec_k": 3}),
        ("mla", "minicpm3-4b", {}, {})]:
    cfg = b.get_smoke_config(arch)
    if flags:
        cfg = dataclasses.replace(cfg, **flags)
    e1, t1 = run(cfg, None, **kw)
    e2, t2 = run(cfg, mesh, **kw)
    kvb = e2.kv_pool_bytes()
    out[name] = {
        "parity": t1 == t2,
        "one_transfer": e2.d2h_transfers == e2.steps,
        "kv_ratio": kvb["kv_pool_bytes_per_device"] / kvb["kv_pool_bytes"],
        "block_leaks": e2.block_leaks(),
        "prefix_hits": e2.prefix_hit_tokens,
    }

# partition rules: pools on the head/latent dim, tables replicated,
# row-parallel params (wo/down) replicated, column-parallel sharded
cfg = b.get_smoke_config("starcoder2-3b")
state = init_decode_state(cfg, 2, 64, kv="paged", num_blocks=9,
                          block_size=8)
sh = serve_state_shardings(state, mesh, cfg)
specs = {}
def walk(path, node):
    if isinstance(node, dict):
        for k, v in node.items():
            walk(path + (k,), v)
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            walk(path + (str(i),), v)
    else:
        specs["/".join(path)] = tuple(node.spec)
walk((), sh)
kp = [v for k, v in specs.items() if k.endswith("kp")]
bt = [v for k, v in specs.items() if k.endswith("block_tables")]
out["state_rules"] = {
    "kp_head_sharded": all(MODEL_AXIS in s and s[-1] == MODEL_AXIS
                           for s in kp) and bool(kp),
    "tables_replicated": all(all(a is None for a in s) for s in bt),
}
params = build_model(cfg).init(jax.random.key(0))
psh = serve_param_shardings(params, mesh)
pspecs = {}
def pwalk(path, node):
    if isinstance(node, dict):
        for k, v in node.items():
            pwalk(path + (k,), v)
    elif isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            pwalk(path + (str(i),), v)
    else:
        pspecs["/".join(path)] = tuple(node.spec)
pwalk((), psh)
wo = [v for k, v in pspecs.items() if k.endswith("wo")]
wq = [v for k, v in pspecs.items() if k.endswith("wq")]
down = [v for k, v in pspecs.items() if k.endswith("down")]
out["param_rules"] = {
    "wo_replicated": all(all(a is None for a in s) for s in wo),
    "down_replicated": all(all(a is None for a in s) for s in down),
    "wq_head_sharded": any(MODEL_AXIS in s for s in wq),
}

# kernel-level shard_map vs single-device bitwise parity
from repro.kernels.paged_attention.ops import (
    paged_decode_attention, paged_decode_attention_tp)
key = jax.random.key(7)
B, nb, bs, K, G, Dh = 2, 9, 8, 2, 2, 16
ks = jax.random.split(key, 4)
q = jax.random.normal(ks[0], (B, K * G, Dh), jnp.bfloat16)
kp = jax.random.normal(ks[1], (nb, bs, K * Dh), jnp.bfloat16)
vp = jax.random.normal(ks[2], (nb, bs, K * Dh), jnp.bfloat16)
tables = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
cache_len = jnp.array([13, 27], jnp.int32)
ref = paged_decode_attention(q, kp, vp, tables, cache_len, kv_heads=K)
tp = paged_decode_attention_tp(q, kp, vp, tables, cache_len, mesh,
                               kv_heads=K)
out["kernel_bitwise"] = bool(
    jnp.all(ref.astype(jnp.float32) == tp.astype(jnp.float32)))

json.dump(out, sys.stdout)
"""


@pytest.mark.slow
def test_tp_serving_battery(tmp_path):
    script = tmp_path / "battery.py"
    script.write_text(_BATTERY)
    r = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=1800,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout)
    for name in ("gqa", "gqa_spec", "mla"):
        rec = out[name]
        assert rec["parity"], (name, rec)             # bitwise tokens
        assert rec["one_transfer"], (name, rec)       # d2h == steps
        assert rec["kv_ratio"] <= 0.6, (name, rec)    # sharded pools
        assert rec["block_leaks"] == 0, (name, rec)   # COW/refcounts
        assert rec["prefix_hits"] > 0, (name, rec)    # churn exercised COW
    assert out["state_rules"] == {"kp_head_sharded": True,
                                  "tables_replicated": True}
    assert out["param_rules"] == {"wo_replicated": True,
                                  "down_replicated": True,
                                  "wq_head_sharded": True}
    assert out["kernel_bitwise"]
