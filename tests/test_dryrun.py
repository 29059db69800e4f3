"""Dry-run machinery: specs, constrain(), layouts, and one real
(subprocess) lower+compile against the production mesh."""

import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, get_config, get_smoke_config
from repro.launch.specs import input_specs

REPO = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins (no allocation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["train", "prefill", "decode"])
def test_input_specs_are_abstract(mode):
    cfg = get_config("gemma-2b")
    specs = input_specs(cfg, SHAPES["decode_32k" if mode == "decode"
                                   else "train_4k"], mode)
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)


def test_train_specs_shapes():
    cfg = get_config("mixtral-8x7b")
    state, batch = input_specs(cfg, SHAPES["train_4k"], "train")
    assert batch["tokens"].shape == (256, 4096)
    n = sum(l.size for l in jax.tree.leaves(state["params"]))
    assert abs(n - cfg.param_count()) / cfg.param_count() < 0.02


def test_decode_specs_cache_rolling_swa():
    cfg = get_config("mixtral-8x7b")             # SWA window 4096
    _, state = input_specs(cfg, SHAPES["long_500k"], "decode")
    (kv,) = [l for l in jax.tree.leaves(state["cache"])
             if l.ndim == 5][:1]
    assert kv.shape[2] == 4096                   # rolling window, not 524288


# ---------------------------------------------------------------------------
# constrain(): no-op without context; correct specs with context
# ---------------------------------------------------------------------------

def test_constrain_noop_without_context():
    from repro.runtime.sharding import constrain
    x = jnp.zeros((4, 8))
    assert constrain(x, "b.") is x


def test_constrain_applies_in_context():
    from repro.runtime.mesh import make_mesh
    from repro.runtime.sharding import activation_sharding, constrain
    mesh = make_mesh((1,), ("data",))
    with activation_sharding(mesh, "2d"):
        out = jax.jit(lambda x: constrain(x, "b."))(jnp.zeros((4, 8)))
    assert out.shape == (4, 8)


def test_serve_mesh_axes_are_auto():
    """The serve mesh must carry Auto axes: ``with_sharding_constraint``
    (behind constrain_replicated) refuses Explicit ones."""
    from jax.sharding import AxisType
    from repro.runtime.mesh import serve_mesh
    from repro.runtime.sharding import (activation_sharding,
                                        constrain_replicated)
    mesh = serve_mesh((1, 1))
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    with activation_sharding(mesh, "serve"):
        out = jax.jit(constrain_replicated)(jnp.ones((2, 3, 4)))
    np.testing.assert_array_equal(np.asarray(out), np.ones((2, 3, 4)))


def test_constrain_conflicting_axes_skipped():
    from repro.runtime.mesh import make_mesh
    from repro.runtime.sharding import activation_sharding, constrain
    mesh = make_mesh((1,), ("data",))
    x = jnp.zeros((4, 4))
    with activation_sharding(mesh, "2d"):
        # batch and expert dims both want "data" -> constraint skipped
        out = constrain(x, "bd")
        assert out is x


# ---------------------------------------------------------------------------
# serve-mesh accounting: per-shard memory/FLOPs without building the mesh
# ---------------------------------------------------------------------------

def test_serve_cell_per_shard_accounting():
    # importing dryrun sets XLA_FLAGS at module top, but jax is already
    # initialized in the test process so the env write is inert here
    from repro.launch.dryrun import run_serve_cell

    one = run_serve_cell("smollm-360m", mesh_shape=(1, 1), slots=4,
                         max_len=64, smoke=True)
    # a 1-device mesh: per-device == total, everything accounted
    assert one["params_bytes_per_device"] == one["params_bytes"] > 0
    assert one["state_bytes_per_device"] == one["state_bytes"] > 0
    assert 0 < one["kv_pool_bytes"] <= one["state_bytes"]

    two = run_serve_cell("minicpm3-4b", mesh_shape=(1, 2), slots=2,
                         max_len=64, smoke=True)
    # MLA paged pools split their latent dim over 2 model shards
    assert two["kv_pool_bytes_per_device"] * 2 == two["kv_pool_bytes"]
    # column-parallel params shard, row-parallel replicate: strictly
    # between the all-replicated and all-sharded extremes
    assert (two["params_bytes"] // 2
            < two["params_bytes_per_device"] < two["params_bytes"])
    assert two["decode_flops_per_device"] * 2 == two["decode_flops"]
    assert two["mesh_devices"] == 2


def test_serve_shard_factors_mirror_sharding_rules():
    """The pure divisor helpers agree with the real serve shardings: a
    leaf's factor is the model-axis size exactly when the named rule's
    dim divides, else 1 (replication)."""
    from repro.configs.base import get_smoke_config
    from repro.models.api import init_decode_state
    from repro.runtime import sharding as shd

    cfg = get_smoke_config("minicpm3-4b")
    state = jax.eval_shape(lambda: init_decode_state(cfg, 2, 64, kv="paged"))
    factors = {}

    def one(path, leaf):
        name = shd._leaf_name(path)
        factors.setdefault(name, set()).add(
            shd.serve_state_shard_factor(path, leaf.shape, 2, cfg))
    jax.tree_util.tree_map_with_path(one, state)
    # MLA latent pools split; control leaves replicate
    assert factors["ckvp"] == {2} and factors["kropep"] == {2}
    assert factors["pos"] == {1} and factors["block_tables"] == {1}
    # msz=1 never shards anything
    def check_one(path, leaf):
        assert shd.serve_state_shard_factor(path, leaf.shape, 1, cfg) == 1
    jax.tree_util.tree_map_with_path(check_one, state)


# ---------------------------------------------------------------------------
# the real thing: one cheap cell lowered+compiled on the 16x16 mesh in a
# subprocess (XLA_FLAGS isolation)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "mamba2-370m", "--shape", "decode_32k"],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
        cwd=str(REPO))
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads((REPO / "results" / "dryrun" / "pod16x16" /
                      "mamba2-370m__decode_32k.json").read_text())
    assert rec["mesh"]["shape"] == [16, 16]
    t = rec["roofline"]
    assert t["compute_s"] > 0 and t["memory_s"] > 0
    assert rec["hlo_cost"]["flops"] > 0
