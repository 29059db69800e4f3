"""Numerics of the model substrate: attention impls, fused loss, SSD scan,
MoE dispatch — including hypothesis property tests on the invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.configs.base import MoESpec, SSMSpec, get_smoke_config
from repro.kernels.flash_attention.ref import attention_ref
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (lm_logits, softmax_cross_entropy,
                                 softmax_cross_entropy_fused)

KEY = jax.random.key(7)


def _r(shape, k, scale=1.0, dtype=jnp.float32):
    return (jax.random.normal(jax.random.fold_in(KEY, k), shape) * scale
            ).astype(dtype)


# ---------------------------------------------------------------------------
# attention implementations agree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,H,K,Dh,window,chunk", [
    (128, 4, 2, 64, None, 32),
    (96, 6, 3, 32, None, 64),
    (128, 4, 4, 64, 48, 32),
])
def test_chunked_attention_matches_dense(S, H, K, Dh, window, chunk):
    q, k, v = (_r((2, S, H, Dh), i) for i in range(3))
    k = _r((2, S, K, Dh), 4)
    v = _r((2, S, K, Dh), 5)
    out = attn.chunked_attention(q, k, v, causal=True, window=window,
                                 chunk=chunk)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)


@pytest.mark.parametrize("S,window", [(128, None), (256, None), (256, 64)])
def test_causal_blocked_matches_chunked(S, window):
    q = _r((1, S, 4, 64), 6)
    k = _r((1, S, 2, 64), 7)
    v = _r((1, S, 2, 64), 8)
    a = attn.causal_blocked_attention(q, k, v, window=window, chunk=32,
                                      block_q=64)
    b = attn.chunked_attention(q, k, v, causal=True, window=window, chunk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=5e-2, atol=2e-2)


def test_decode_attend_matches_dense_row():
    """decode_attend == last row of full attention with same cache."""
    B, T, H, K, Dh = 2, 64, 4, 2, 32
    q = _r((B, 1, H, Dh), 9)
    kc = _r((B, T, K, Dh), 10)
    vc = _r((B, T, K, Dh), 11)
    out = attn.decode_attend(q, kc, vc, jnp.int32(T))
    ref = attention_ref(q, kc, vc, causal=True, q_offset=T - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# fused CE loss (hypothesis property tests)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 3),
    s=st.integers(2, 130),
    v=st.integers(5, 120),
    chunk=st.sampled_from([16, 32, 64]),
    softcap=st.sampled_from([None, 30.0]),
)
def test_fused_ce_equals_dense(b, s, v, chunk, softcap):
    d = 16
    h = _r((b, s, d), 20, dtype=jnp.float32)
    head = _r((d, v), 21, scale=0.2)
    t = jax.random.randint(jax.random.fold_in(KEY, 22), (b, s), 0, v)
    dense = softmax_cross_entropy(lm_logits(h, head, softcap), t)
    fused = softmax_cross_entropy_fused(h, head, t, softcap=softcap,
                                        chunk=chunk)
    np.testing.assert_allclose(float(dense), float(fused), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(40, 90), frac=st.floats(0.1, 0.9))
def test_fused_ce_mask_semantics(s, frac):
    b, d, v = 2, 16, 50
    h = _r((b, s, d), 23)
    head = _r((d, v), 24, scale=0.2)
    t = jax.random.randint(jax.random.fold_in(KEY, 25), (b, s), 0, v)
    mask = (jax.random.uniform(jax.random.fold_in(KEY, 26), (b, s))
            < frac).astype(jnp.float32)
    dense = softmax_cross_entropy(lm_logits(h, head, None), t, mask)
    fused = softmax_cross_entropy_fused(h, head, t, mask=mask, chunk=32)
    np.testing.assert_allclose(float(dense), float(fused), rtol=1e-5,
                               atol=1e-5)


def test_fused_ce_gradients_match():
    b, s, d, v = 2, 96, 16, 64
    h = _r((b, s, d), 27)
    head = _r((d, v), 28, scale=0.2)
    t = jax.random.randint(jax.random.fold_in(KEY, 29), (b, s), 0, v)
    g1 = jax.grad(lambda hh: softmax_cross_entropy(
        lm_logits(hh, head, None), t))(h)
    g2 = jax.grad(lambda hh: softmax_cross_entropy_fused(
        hh, head, t, chunk=32))(h)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-4, atol=2e-6)


# ---------------------------------------------------------------------------
# SSD scan invariants
# ---------------------------------------------------------------------------

def _ssm_cfg(**kw):
    base = get_smoke_config("mamba2-370m")
    return dataclasses.replace(base, ssm=SSMSpec(**{**dict(
        state_dim=base.ssm.state_dim, head_dim=base.ssm.head_dim,
        expand=base.ssm.expand, conv_width=base.ssm.conv_width,
        chunk_size=base.ssm.chunk_size, n_groups=base.ssm.n_groups), **kw}))


@settings(max_examples=8, deadline=None)
@given(chunk_a=st.sampled_from([16, 32, 64]),
       chunk_b=st.sampled_from([16, 32, 128]),
       s=st.integers(33, 130))
def test_ssd_chunk_size_invariance(chunk_a, chunk_b, s):
    """The chunked SSD evaluation must not depend on the chunk size."""
    b, H, P, N = 1, 2, 32, 64
    x = _r((b, s, H, P), 30)
    dt = jax.nn.softplus(_r((b, s, H), 31))
    A = -jnp.exp(_r((H,), 32, scale=0.3))
    B = _r((b, s, 1, N), 33, scale=0.3)
    C = _r((b, s, 1, N), 34, scale=0.3)
    ya, sa = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk_a)
    yb, sb = ssm_mod.ssd_chunked(x, dt, A, B, C, chunk_b)
    np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                               rtol=2e-4, atol=2e-4)


def test_ssm_prefill_state_matches_decode():
    """Running prefill over S tokens then decoding one more must equal a
    prefill over S+1 tokens (state-carry correctness)."""
    cfg = get_smoke_config("mamba2-370m")
    p = ssm_mod.init_ssm(jax.random.fold_in(KEY, 35), cfg)
    S = 24
    x = _r((1, S + 1, cfg.d_model), 36, scale=0.5, dtype=jnp.bfloat16)
    out_full, _ = ssm_mod.ssm_forward_with_cache(x, p, cfg)
    _, cache = ssm_mod.ssm_forward_with_cache(x[:, :S], p, cfg)
    out_step, _ = ssm_mod.ssm_decode(x[:, S:S + 1], p, cfg, cache)
    np.testing.assert_allclose(
        np.asarray(out_step[:, 0], np.float32),
        np.asarray(out_full[:, S], np.float32), rtol=0.1, atol=0.1)


# ---------------------------------------------------------------------------
# MoE dispatch invariants
# ---------------------------------------------------------------------------

def test_moe_capacity_dispatch_matches_dense():
    """The dropless dispatch equals dense all-experts gating: every token
    reaches all of its top-k experts, with no capacity factor to raise."""
    cfg = get_smoke_config("mixtral-8x7b")
    p = moe_mod.init_moe(jax.random.fold_in(KEY, 37), cfg)
    x = _r((2, 16, cfg.d_model), 38, scale=0.5, dtype=jnp.bfloat16)
    y_sorted, _ = moe_mod.apply_moe(x, p, cfg)
    y_dense, _ = moe_mod.apply_moe_dense(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y_sorted, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=0.1, atol=0.05)


def test_moe_skewed_router_drops_no_token():
    """A router that sends every token to the same top-k experts loads
    each of them with all S tokens, far past the old 1.25x capacity
    (S*k/E*1.25 = 80 of 128 here): the dropless layer still equals
    per-token dense gating, where a capacity layer would have zeroed the
    pairs past its capacity."""
    cfg = get_smoke_config("granite-moe-3b-a800m")
    m = cfg.moe
    S = 128
    p = moe_mod.init_moe(jax.random.fold_in(KEY, 41), cfg)
    p = {**p, "router": jnp.zeros_like(p["router"]).at[:, :m.top_k].set(
        jnp.linspace(4.0, 1.0, m.top_k))}
    x = jnp.abs(_r((1, S, cfg.d_model), 42, scale=0.5)).astype(jnp.bfloat16)
    probs = moe_mod.router_probs(x, p["router"])
    _, idx = jax.lax.top_k(probs, m.top_k)
    assert set(np.unique(np.asarray(idx)).tolist()) == set(range(m.top_k))
    assert S > S * m.top_k / m.num_experts * 1.25
    y, _ = moe_mod.apply_moe(x, p, cfg)
    y_dense, _ = moe_mod.apply_moe_dense(x, p, cfg)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=0.05, atol=0.02)
    assert float(jnp.min(jnp.linalg.norm(y.astype(jnp.float32), axis=-1))) > 0


def test_moe_kernel_gradient_matches_jnp_product(monkeypatch):
    """The Pallas grouped matmul (interpreted here) has a backward of its
    own: gradients through it equal those through the jnp product."""
    cfg = get_smoke_config("granite-moe-3b-a800m")
    p = moe_mod.init_moe(jax.random.fold_in(KEY, 43), cfg)
    x = _r((2, 16, cfg.d_model), 44, scale=0.5, dtype=jnp.bfloat16)

    def loss(p, x):
        return jnp.sum(moe_mod.apply_moe(x, p, cfg)[0].astype(jnp.float32)
                       ** 2)

    want = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(moe_mod, "_kernel_ok", lambda: True)
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0.02, atol=0.01)


def test_moe_aux_loss_uniform_router_is_one():
    """Switch aux loss == router_aux_weight when routing is uniform."""
    cfg = get_smoke_config("granite-moe-3b-a800m")
    E = cfg.moe.num_experts
    p = moe_mod.init_moe(jax.random.fold_in(KEY, 39), cfg)
    p = {**p, "router": jnp.zeros_like(p["router"])}       # uniform probs
    x = _r((1, 64, cfg.d_model), 40, dtype=jnp.bfloat16)
    _, aux = moe_mod.apply_moe(x, p, cfg)
    # me = 1/E; ce sums to k tokens spread evenly -> aux = w * E * sum(me*ce/k)
    np.testing.assert_allclose(float(aux), cfg.moe.router_aux_weight,
                               rtol=0.15)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(8, 64))
def test_moe_capacity_bounds(s):
    """The dropless buffer holds every pair whatever the routing:
    S*k <= rows <= S*k + E*(tile-1), in whole tiles, and the placement
    fills each expert's tiles with its own pairs only."""
    cfg = get_smoke_config("mixtral-8x7b")
    E, k = cfg.moe.num_experts, cfg.moe.top_k
    rows, tile = moe_mod.dropless_rows(s, cfg)
    assert s * k <= rows <= s * k + E * (tile - 1)
    assert rows % tile == 0 and tile % 16 == 0
    idx = jax.random.randint(jax.random.fold_in(KEY, s), (s, k), 0, E)
    dest, tile_ids = moe_mod._sort_pairs(idx, E, rows, tile)
    dest = np.asarray(dest)
    assert len(set(dest.tolist())) == s * k and dest.max() < rows
    assert np.array_equal(np.asarray(tile_ids)[dest // tile],
                          np.asarray(idx).reshape(-1))
