"""Main-path kernels compile for a TPU v5e at published widths.

Interpret mode (every other kernel test) cannot see the TPU compiler's
tiling and VMEM rules; this file compiles each kernel for one chip of a
described ``v5e:2x2`` topology, with no chip attached.  The topology is
described inside a module-scoped fixture — never at import, in a
``skipif`` or in ``parametrize`` — so every xdist worker collects the same
tests and only the worker running this file loads the TPU compiler.
Nothing runs: a pass says the compiler accepts the kernel, not that its
results are right (``chip_smoke.py`` checks that on the chip).
"""

import importlib.util
import pathlib

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any refusal means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of any cache in use
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _paged(arch, sd, *, n_q=None):
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_verify_attention, row_width)
    cfg = get_config(arch)
    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, bs, mb = 8, 16, 64                      # 8 slots x 1024 tokens
    nb = B * mb + 1
    pool = sd((nb, bs, row_width(K, Dh)), jnp.bfloat16)
    tables, rows = sd((B, mb), jnp.int32), sd((B,), jnp.int32)
    if n_q is None:
        return (lambda *a: paged_decode_attention(*a, kv_heads=K,
                                                  interpret=False),
                (sd((B, H, Dh), jnp.bfloat16), pool, pool, tables, rows))
    return (lambda *a: paged_verify_attention(*a, kv_heads=K,
                                              interpret=False),
            (sd((B, n_q, H, Dh), jnp.bfloat16), pool, pool, tables, rows))


def _decode_attention(sd):
    from repro.kernels.decode_attention.ops import decode_attention
    cfg = get_config("smollm-360m")
    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cache = sd((8, 1024, K, Dh), jnp.bfloat16)
    return (lambda *a: decode_attention(*a, interpret=False),
            (sd((8, H, Dh), jnp.bfloat16), cache, cache,
             sd((8,), jnp.int32)))


def _flash(sd):
    from repro.kernels.flash_attention.ops import flash_attention
    cfg = get_config("smollm-360m")
    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv = sd((1, 2048, K, Dh), jnp.bfloat16)
    return (lambda *a: flash_attention(*a, interpret=False),
            (sd((1, 2048, H, Dh), jnp.bfloat16), kv, kv))


def _rmsnorm(sd):
    from repro.kernels.rmsnorm.ops import rmsnorm_fused
    D = get_config("smollm-360m").d_model
    return (lambda *a: rmsnorm_fused(*a, interpret=False),
            (sd((2048, D), jnp.bfloat16), sd((D,), jnp.float32)))


def _grouped_matmul(sd):
    from repro.kernels.grouped_matmul.ops import grouped_matmul
    cfg = get_config("granite-moe-3b-a800m")
    E, F = cfg.moe.num_experts, cfg.moe.d_ff_expert
    return (lambda *a: grouped_matmul(*a, interpret=False),
            (sd((2048, cfg.d_model), jnp.bfloat16),
             sd((E, cfg.d_model, F), jnp.bfloat16), sd((E,), jnp.int32)))


def _ssd_scan(sd):
    from repro.kernels.ssd_scan.ops import ssd_scan
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    H = s.expand * cfg.d_model // s.head_dim
    S = 2048
    return (lambda *a: ssd_scan(*a, chunk=s.chunk_size, interpret=False),
            (sd((1, S, H, s.head_dim), jnp.bfloat16),
             sd((1, S, H), jnp.float32), sd((H,), jnp.float32),
             sd((1, S, s.n_groups, s.state_dim), jnp.bfloat16),
             sd((1, S, s.n_groups, s.state_dim), jnp.bfloat16)))


CASES = {
    "paged_decode-smollm-360m": lambda sd: _paged("smollm-360m", sd),
    "paged_decode-starcoder2-3b": lambda sd: _paged("starcoder2-3b", sd),
    "paged_verify-smollm-360m": lambda sd: _paged("smollm-360m", sd, n_q=5),
    "paged_verify-starcoder2-3b": lambda sd: _paged("starcoder2-3b", sd,
                                                    n_q=5),
    "decode_attention-smollm-360m": _decode_attention,
    "flash_attention-smollm-360m": _flash,
    "rmsnorm-smollm-360m": _rmsnorm,
    "grouped_matmul-granite-moe-3b-a800m": _grouped_matmul,
    "ssd_scan-mamba2-370m": _ssd_scan,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    fn, args = CASES[case](sd)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), case


def test_dropless_moe_layer_compiles_for_v5e(one_chip, monkeypatch):
    """The dropless MoE layer at granite-moe-3b-a800m widths over one
    2048-token prompt compiles for a v5e with the grouped-matmul kernel
    in it.  The layer asks the backend whether to take the kernel and
    whether to interpret it; this process's backend is the CPU, so the
    test tells it the TPU's answer."""
    from repro.models import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("granite-moe-3b-a800m")
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=one_chip)
    p = jax.tree.map(lambda a: sd(a.shape, a.dtype), jax.eval_shape(
        lambda: moe.init_moe(jax.random.key(0), cfg)))
    x = sd((1, 2048, cfg.d_model), jnp.bfloat16)
    compiled = jax.jit(lambda x, p: moe.apply_moe(x, p, cfg)[0]).lower(
        x, p).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3       # up, gate and down
    assert "%grouped_matmul" in text


def test_decode_step_reads_pools_in_place(one_chip, monkeypatch):
    """The engine's decode step for the benchmark's 32x2048 smollm-360m
    image, compiled for a v5e, holds no copy shaped like a KV pool: the
    pools are stored in the layout the paged kernel streams, and the step
    writes its new cache as its output.  The step asks the backend whether
    to interpret the kernel; this process's backend is the CPU, so the
    test tells it the TPU's answer."""
    import dataclasses
    import repro.kernels.paged_attention.ops as paged_ops
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    monkeypatch.setattr(paged_ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("smollm-360m"), attn_impl="pallas")
    copies = chip_smoke.decode_step_pool_copies(
        cfg, chip_smoke.POOL_SLOTS, chip_smoke.POOL_MAX_LEN,
        sharding=one_chip)
    assert copies == []
