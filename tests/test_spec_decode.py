"""Speculative decoding: draft-and-verify multi-token steps with paged
rollback.

The load-bearing property is BITWISE EQUALITY: greedy draft-and-verify
commits exactly the tokens sequential greedy decode would produce, for any
draft model — the draft only changes how many positions each step
advances, never which tokens are committed.  On top of that: the k-query
verify kernel vs its jnp oracle, rejected-suffix rollback never touching a
shared prefix block (copy-on-write property), the one-transfer-per-step
contract surviving the multi-token return (asserted by intercepting
device->host pulls at the ArrayImpl layer), cancel-mid-verify releasing
every draft-extended block (leak/underflow guard), SSM/SWA archs falling
back non-speculative with a recorded reason, and the fleet path replaying
requeued requests bitwise with speculation on.

Pure-function tests run in the fast lane; everything that builds a full
model engine carries @pytest.mark.slow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.serving.engine import Request, ServeEngine, spec_ineligible_reason


def _params(cfg):
    from repro.models.api import build_model
    return build_model(cfg).init(jax.random.key(0))


def _reqs(n=4, vocab=500):
    rng = np.random.default_rng(0)
    lens = [7, 20, 3, 31, 12, 25]
    buds = [9, 13, 17, 5, 11, 7]
    return [Request(rid=i,
                    prompt=rng.integers(1, vocab, size=lens[i % 6]).astype(
                        np.int32),
                    max_new_tokens=buds[i % 6]) for i in range(n)]


# ---------------------------------------------------------------------------
# fast lane: eligibility gating + the verify kernel vs its oracle
# ---------------------------------------------------------------------------

def test_spec_ineligible_reasons():
    gqa = get_smoke_config("smollm-360m")
    assert spec_ineligible_reason(gqa, "paged") is None
    assert "paged" in spec_ineligible_reason(gqa, "dense")
    assert "SSM" in spec_ineligible_reason(
        get_smoke_config("mamba2-370m"), "paged")
    assert "SSM" in spec_ineligible_reason(
        get_smoke_config("jamba-v0.1-52b"), "paged")
    assert "SWA" in spec_ineligible_reason(
        get_smoke_config("mixtral-8x7b"), "paged")
    assert "enc-dec" in spec_ineligible_reason(
        get_smoke_config("whisper-small"), "paged")


@pytest.mark.parametrize("B,S,H,K,Dh,bs,mb", [
    (2, 5, 4, 2, 16, 8, 4),
    (3, 3, 4, 4, 8, 16, 2),
    (1, 5, 8, 1, 32, 8, 3),               # MQA-style grouping
])
def test_paged_verify_kernel_matches_ref(B, S, H, K, Dh, bs, mb):
    from repro.kernels.paged_attention.ops import (paged_verify_attention,
                                                   row_width)
    from repro.kernels.paged_attention.ref import paged_verify_attention_ref

    rng = np.random.default_rng(0)
    nb = B * mb + 1
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, row_width(K, Dh))),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, row_width(K, Dh))),
                     jnp.float32)
    tables = jnp.asarray(
        1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    off = jnp.asarray(rng.integers(0, mb * bs - S, size=(B,)), jnp.int32)
    out = paged_verify_attention(q, kp, vp, tables, off, kv_heads=K,
                                 interpret=True)
    ref = paged_verify_attention_ref(q, kp, vp, tables, off, kv_heads=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=2e-2)


def test_paged_verify_kernel_overflow_positions_are_safe():
    """Query positions past the table's reach (off + s >= mb*bs) must not
    crash or poison finite rows — acceptance clamps them away, but the
    kernel still computes them."""
    from repro.kernels.paged_attention.ops import (paged_verify_attention,
                                                   row_width)
    from repro.kernels.paged_attention.ref import paged_verify_attention_ref

    rng = np.random.default_rng(1)
    B, S, H, K, Dh, bs, mb = 3, 5, 4, 2, 16, 8, 4
    nb = B * mb + 1
    q = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, row_width(K, Dh))),
                     jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, row_width(K, Dh))),
                     jnp.float32)
    tables = jnp.asarray(1 + np.arange(B * mb).reshape(B, mb), jnp.int32)
    off = jnp.asarray([mb * bs - 2, mb * bs - 1, mb * bs - 3], jnp.int32)
    out = paged_verify_attention(q, kp, vp, tables, off, kv_heads=K,
                                 interpret=True)
    ref = paged_verify_attention_ref(q, kp, vp, tables, off, kv_heads=K)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=2e-2)


# ---------------------------------------------------------------------------
# bitwise equality with sequential greedy decode (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", ["smollm-360m", "minicpm3-4b"])
def test_spec_tokens_bitwise_equal_off(arch):
    """Self-draft (acceptance ~1) and a cold random draft (acceptance ~0)
    both commit exactly the spec="off" greedy tokens — per arch family
    (dense GQA and MLA latent attention)."""
    cfg = get_smoke_config(arch)
    from repro.models.api import build_model
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    base = ServeEngine(cfg, params, slots=3, max_len=64, bundle=bundle)
    for r in _reqs(5):
        base.submit(r)
    base.run()
    for draft_cfg in (None, get_smoke_config(arch)):
        eng = ServeEngine(cfg, params, slots=3, max_len=64, bundle=bundle,
                          spec="draft", spec_k=4, draft_cfg=draft_cfg)
        assert eng.spec == "draft", eng.spec_fallback_reason
        for r in _reqs(5):
            eng.submit(r)
        stats = eng.run()
        for rid in range(5):
            assert eng.done[rid].tokens == base.done[rid].tokens, rid
        assert stats["d2h_transfers"] == stats["decode_steps"]
        if draft_cfg is None:              # self-draft: acceptance is high
            assert stats["acceptance_rate"] > 0.5
            assert stats["tokens_per_step"] > 1.0


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["mamba2-370m", "mixtral-8x7b"])
def test_spec_falls_back_on_ssm_swa(arch):
    """Archs whose per-token state cannot roll back serve non-speculatively
    with a recorded reason — and their tokens still match spec="off"."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64,
                      spec="draft", spec_k=4)
    assert eng.spec == "off"
    assert eng.spec_fallback_reason is not None
    base = ServeEngine(cfg, params, slots=2, max_len=64)
    for r in _reqs(3):
        eng.submit(r)
    for r in _reqs(3):
        base.submit(r)
    stats = eng.run()
    base.run()
    for rid in range(3):
        assert eng.done[rid].tokens == base.done[rid].tokens
    assert stats["spec"] == "off"
    assert stats["spec_fallback_reason"] == eng.spec_fallback_reason


# ---------------------------------------------------------------------------
# rollback never corrupts a shared prefix (COW property, slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_rollback_never_corrupts_shared_prefix():
    """Two slots share a prompt-prefix block and decode speculatively; the
    draft/verify frontier extensions and every rejected-suffix rollback
    must leave the shared block's pool contents bitwise untouched, in the
    TARGET pools and the shadow DRAFT pools alike — and refcounts must
    balance back to prefix-only pins."""
    cfg = get_smoke_config("smollm-360m")
    from repro.models.api import build_model
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, bundle=bundle,
                      spec="draft", spec_k=4)
    assert eng.spec == "draft"
    rng = np.random.default_rng(7)
    prompt = rng.integers(1, 500, size=30).astype(np.int32)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=12))
    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=12))
    eng._admit()
    shared = set(eng._slot_blocks[0]) & set(eng._slot_blocks[1])
    assert shared, "prompts must share a prefix block"
    ids = sorted(shared)
    snap_t = [{k: np.asarray(leaf[k][:, ids]) for k in ("kp", "vp")}
              for leaf in eng.state["cache"]]
    snap_d = [{k: np.asarray(leaf[k][:, ids]) for k in ("kp", "vp")}
              for leaf in eng._draft_cache]
    eng.run()
    assert len(eng.done) == 2
    assert eng.done[0].tokens == eng.done[1].tokens   # same prompt, greedy
    for leaf, snap in zip(eng.state["cache"], snap_t):
        for k in ("kp", "vp"):
            np.testing.assert_array_equal(np.asarray(leaf[k][:, ids]),
                                          snap[k])
    for leaf, snap in zip(eng._draft_cache, snap_d):
        for k in ("kp", "vp"):
            np.testing.assert_array_equal(np.asarray(leaf[k][:, ids]),
                                          snap[k])
    # refcount balance: only prefix-cache pins remain; flushing them
    # returns the pool to empty (any leak or double-free shows up here)
    assert eng.allocator.allocated_blocks == len(eng.prefix._map)
    eng.prefix.evict_unreferenced(eng.allocator.capacity_blocks)
    assert eng.allocator.allocated_blocks == 0


# ---------------------------------------------------------------------------
# one transfer per step, even with k+1 tokens riding it (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_spec_one_transfer_per_step():
    """The packed (k+3, slots) verify return is the ONLY device->host pull
    per decode step: intercept ArrayImpl materialization and count."""
    import jax._src.array as jarr

    cfg = get_smoke_config("smollm-360m")
    from repro.models.api import build_model
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, bundle=bundle,
                      spec="draft", spec_k=4)
    assert eng.spec == "draft"
    for i in range(2):                     # one admission wave, equal budget
        eng.submit(Request(rid=i,
                           prompt=(np.arange(9) + 3 * i + 1).astype(np.int32),
                           max_new_tokens=10))
    eng.step()                             # admissions + first decode step
    pulls = []
    orig = jarr.ArrayImpl.__dict__["_value"]

    def counting(self):
        pulls.append(1)
        return orig.fget(self)

    jarr.ArrayImpl._value = property(counting)
    try:
        steps = 0
        while eng._live:
            eng.step()
            steps += 1
    finally:
        jarr.ArrayImpl._value = orig
    assert steps > 0
    assert len(pulls) == steps, (len(pulls), steps)
    assert eng.d2h_transfers == eng.steps


# ---------------------------------------------------------------------------
# cancel-mid-verify: draft-extended blocks release exactly once (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cancel_mid_verify_releases_draft_extended_blocks():
    """Churn loop: admit, speculate a few steps (the verify frontier is now
    up to k past the committed one in both pools), cancel mid-flight,
    repeat.  Every block must come back exactly once — the allocator
    raises on double-free, and anything leaked shows up as a nonzero
    residue after flushing the prefix pins."""
    cfg = get_smoke_config("smollm-360m")
    from repro.models.api import build_model
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, bundle=bundle,
                      spec="draft", spec_k=4)
    assert eng.spec == "draft"
    rng = np.random.default_rng(3)
    rid = 0
    for round_ in range(4):
        for _ in range(2):
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(1, 500, size=17).astype(np.int32),
                max_new_tokens=20))
            rid += 1
        eng.step()                         # admit + first speculative step
        eng.step()                         # mid-verify state on device
        for r in (rid - 2, rid - 1):
            if r in eng._live:
                assert eng.cancel(r) is not None
        eng.done.clear()
        assert not eng._live
        # only prefix pins may remain allocated between rounds
        assert eng.allocator.allocated_blocks == len(eng.prefix._map)
    eng.prefix.evict_unreferenced(eng.allocator.capacity_blocks)
    assert eng.allocator.allocated_blocks == 0


# ---------------------------------------------------------------------------
# the fleet path: kill a pilot with speculation on (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_requeue_replays_bitwise_with_spec_on():
    """Kill 1 of 3 speculative serving pilots mid-trace: every request
    completes exactly once, and the tokens match both a no-failure
    speculative run AND a non-speculative fleet run bitwise (the image's
    fixed draft seed makes every server draft identically)."""
    from repro.core.images import ExecutableRegistry
    from repro.launch.serve import serve_fleet

    registry = ExecutableRegistry()
    plain = serve_fleet("smollm-360m", 10, 3, slots=2, max_len=64,
                        lease_ttl=0.5, registry=registry)
    ok = serve_fleet("smollm-360m", 10, 3, slots=2, max_len=64,
                     lease_ttl=0.5, registry=registry, draft="self")
    failed = serve_fleet("smollm-360m", 10, 3, slots=2, max_len=64,
                         fail_at=2, lease_ttl=0.5, registry=registry,
                         draft="self")
    assert ok["completed"] == 10 and ok["replays"] == 0
    assert ok["spec_servers"] == 3
    assert ok["acceptance_rate"] > 0.0
    assert failed["completed"] == 10
    assert len(failed["failed_pilots"]) == 1
    assert sorted(failed["results"]) == list(range(10))
    assert failed["results"] == ok["results"] == plain["results"]
    assert failed["replays"] >= 1
