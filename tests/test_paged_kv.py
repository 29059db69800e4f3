"""Paged KV cache: allocator invariants, prefix-cache refcounts, kernel
vs oracle, paged-decode bitwise parity with the dense slab across every
arch family, copy-on-write safety of shared prefix blocks, chunked-prefill
interleaving, and the admit-length boundary.

Allocator/prefix/kernel/attention tests run in the fast lane; everything
that builds a full model engine carries @pytest.mark.slow.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config, list_archs
from repro.serving.blockpool import BlockAllocator, PrefixCache
from repro.serving.engine import (
    Request, ServeEngine, admit_buckets, admit_length, prefill_chunk_shapes)


def _params(cfg):
    from repro.models.api import build_model
    return build_model(cfg).init(jax.random.key(0))


def _req(rid, plen, max_new, vocab=512, seed=None):
    rng = np.random.default_rng(seed if seed is not None else rid)
    return Request(rid=rid,
                   prompt=rng.integers(0, vocab, size=plen).astype(np.int32),
                   max_new_tokens=max_new)


# ---------------------------------------------------------------------------
# allocator invariants (fast lane)
# ---------------------------------------------------------------------------

def test_allocator_roundtrip_and_scratch_reserved():
    a = BlockAllocator(num_blocks=5, block_size=16)
    assert a.capacity_blocks == 4            # block 0 is scratch
    bids = [a.alloc() for _ in range(4)]
    assert 0 not in bids
    assert a.allocated_blocks == 4
    with pytest.raises(RuntimeError):
        a.alloc()                            # exhausted
    for b in bids:
        a.free(b)
    assert a.allocated_blocks == 0
    assert a.available_blocks == 4


def test_allocator_refcount_never_negative():
    a = BlockAllocator(num_blocks=4, block_size=16)
    b = a.alloc()
    a.free(b)
    with pytest.raises(RuntimeError):
        a.free(b)                            # double free
    # scratch block frees are no-ops, never underflow
    a.free(0)
    a.free(0)


def test_allocator_share_keeps_block_live():
    a = BlockAllocator(num_blocks=4, block_size=16)
    b = a.alloc()
    a.share(b)
    assert a.refcount(b) == 2
    a.free(b)
    assert a.allocated_blocks == 1           # still held by the share
    a.free(b)
    assert a.allocated_blocks == 0


# ---------------------------------------------------------------------------
# prefix cache (fast lane)
# ---------------------------------------------------------------------------

def test_prefix_chain_keys_prefix_property():
    toks = np.arange(64, dtype=np.int32)
    keys = PrefixCache.block_keys(toks, 16, 4)
    keys2 = PrefixCache.block_keys(toks.copy(), 16, 4)
    assert keys == keys2                     # deterministic
    diverged = toks.copy()
    diverged[20] = 999                       # inside block 1
    keys3 = PrefixCache.block_keys(diverged, 16, 4)
    assert keys3[0] == keys[0]               # block 0 unchanged
    assert keys3[1] != keys[1]               # chain breaks at the edit...
    assert keys3[2] != keys[2]               # ...and stays broken after


def test_prefix_cache_match_publish_evict():
    a = BlockAllocator(num_blocks=8, block_size=16)
    pc = PrefixCache(a)
    toks = np.arange(48, dtype=np.int32)
    keys = PrefixCache.block_keys(toks, 16, 3)
    owned = [a.alloc() for _ in range(3)]
    for k, b in zip(keys, owned):
        pc.publish(k, b)                     # cache takes one ref each
    assert all(a.refcount(b) == 2 for b in owned)
    for b in owned:                          # request evicted
        a.free(b)
    assert a.allocated_blocks == 3           # cache keeps them alive
    hit = pc.match(keys)
    assert hit == owned                      # longest-prefix, in order
    assert all(a.refcount(b) == 2 for b in owned)
    # a block referenced by a live request survives pressure eviction
    assert pc.evict_unreferenced(10) == 0
    for b in hit:
        a.free(b)
    assert pc.evict_unreferenced(2) == 2     # oldest-first, cache-only
    assert a.allocated_blocks == 1
    pc.clear()
    assert a.allocated_blocks == 0


def test_prefix_cache_partial_match_stops_at_divergence():
    a = BlockAllocator(num_blocks=8, block_size=16)
    pc = PrefixCache(a)
    toks = np.arange(48, dtype=np.int32)
    keys = PrefixCache.block_keys(toks, 16, 3)
    b0 = a.alloc()
    pc.publish(keys[0], b0)
    assert pc.match(keys) == [b0]            # only block 0 cached
    a.free(b0)


# ---------------------------------------------------------------------------
# admit_length boundary + bucket/chunk shape sets (fast lane)
# ---------------------------------------------------------------------------

def test_admit_length_error_states_actual_cap():
    with pytest.raises(ValueError, match="31"):
        admit_length(32, 32)
    with pytest.raises(ValueError, match="95"):
        admit_length(200, 96)


def test_admit_length_boundary_is_admitted():
    assert admit_length(31, 32) == 31        # == max_len - 1: accepted
    assert admit_length(95, 96) == 95
    assert admit_length(5, 32) == 16


def test_admit_buckets_cover_every_prompt_length():
    for max_len in (32, 64, 96, 256):
        buckets = set(admit_buckets(max_len))
        for plen in range(1, max_len):
            assert admit_length(plen, max_len) in buckets, (plen, max_len)


def test_prefill_chunk_shapes_closed_under_prefix_offsets():
    """Aligned chunking from ANY block-boundary start must only produce
    chunk lengths in the precomputed (warmable) set."""
    max_len, bs, chunk = 96, 16, 32
    shapes = set(prefill_chunk_shapes(max_len, bs, chunk))
    for plen in admit_buckets(max_len):
        for start in range(0, plen, bs):
            off = start
            while off < plen:
                C = min(chunk - off % chunk, plen - off)
                assert C in shapes, (plen, start, off, C)
                off += C


# ---------------------------------------------------------------------------
# paged kernel vs oracle (fast lane, interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,Dh,bs,mb", [
    (3, 4, 2, 32, 16, 6),
    (2, 4, 1, 64, 16, 4),        # MQA
    (1, 8, 4, 32, 32, 3),        # bigger blocks
])
def test_paged_kernel_matches_ref(B, H, K, Dh, bs, mb):
    from repro.kernels.paged_attention.ops import (paged_decode_attention,
                                                   row_width)
    from repro.kernels.paged_attention.ref import paged_decode_attention_ref

    rng = np.random.default_rng(0)
    nb = B * mb + 2
    W = row_width(K, Dh)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, W)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, W)), jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, size=(B, mb)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, mb * bs + 1, size=(B,)), jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, lens, kv_heads=K)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, kv_heads=K)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=2e-2)


@pytest.mark.parametrize("K", [1, 5, 8])
@pytest.mark.parametrize("Dh", [64, 128])
def test_paged_kernel_pool_layout_matches_ref(K, Dh):
    """The pool row holds a position's K heads merged, padded to whole
    128-lane rows (5 x 64 = 320 -> 384).  Decode and verify through the
    kernel match the oracle, and the padding lanes are never read: filling
    them with NaN changes nothing."""
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_verify_attention, row_width)
    from repro.kernels.paged_attention.ref import (
        paged_decode_attention_ref, paged_verify_attention_ref)

    W = row_width(K, Dh)
    assert K * Dh <= W < K * Dh + 128 and (W % 128 == 0 or W == K * Dh)
    B, G, S, bs, mb = 2, 3, 3, 16, 3
    H, nb = K * G, B * mb + 1
    rng = np.random.default_rng(K * 1000 + Dh)
    pools = []
    for _ in range(2):
        pool = rng.normal(size=(nb, bs, W)).astype(np.float32)
        pool[..., K * Dh:] = np.nan
        pools.append(jnp.asarray(pool))
    kp, vp = pools
    bt = jnp.asarray(1 + rng.permutation(B * mb).reshape(B, mb), jnp.int32)
    lens = jnp.asarray([5, mb * bs], jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, Dh)), jnp.float32)
    out = paged_decode_attention(q, kp, vp, bt, lens, kv_heads=K)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, kv_heads=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)
    qv = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    off = jnp.asarray([0, mb * bs - S], jnp.int32)
    out = paged_verify_attention(qv, kp, vp, bt, off, kv_heads=K)
    ref = paged_verify_attention_ref(qv, kp, vp, bt, off, kv_heads=K)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# attention-level: paged decode bitwise == dense (fast lane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-360m", "minicpm3-4b"])
def test_attention_decode_paged_bitwise_equals_dense(arch):
    """Scatter a dense cache's rows into a permuted block pool: the paged
    decode (write + gather + attend) must reproduce the dense ring decode
    bit for bit — same shapes, same masks, same reduction order."""
    from repro.models import attention as attn

    cfg = get_smoke_config(arch)
    p = attn.init_attention(jax.random.key(1), cfg)
    B, T, bs = 3, 32, 16
    mb = T // bs
    key = jax.random.key(3)
    dense = {k: (jax.random.normal(jax.random.fold_in(key, i), v.shape,
                                   jnp.float32) * 0.1).astype(v.dtype)
             for i, (k, v) in enumerate(
                 attn.init_kv_cache(cfg, B, T).items())}
    nb = B * mb + 1
    perm = np.random.default_rng(0).permutation(np.arange(1, nb))
    bt = jnp.asarray(perm.reshape(B, mb), jnp.int32)
    paged = _paged_from_dense(cfg, dense, bt, nb, bs)
    x = jax.random.normal(jax.random.key(2), (B, 1, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.asarray([2, 17, 30], jnp.int32)
    out_d, _ = attn.attention_decode(x, p, cfg, dense, pos)
    out_p, _ = attn.attention_decode(x, p, cfg, paged, pos, block_tables=bt)
    np.testing.assert_array_equal(np.asarray(out_d, np.float32),
                                  np.asarray(out_p, np.float32))


def _paged_from_dense(cfg, dense, bt, nb, bs):
    """Scatter a dense cache's rows (B, T, ...) into block pools in the
    engine's pool layout, row b's block j at physical id ``bt[b, j]``."""
    from repro.models import attention as attn

    to_paged = {"k": "kp", "v": "vp", "ckv": "ckvp", "krope": "kropep"}
    pools = attn.init_kv_cache_paged(cfg, bt.shape[0], bt.size * bs, nb,
                                     bs)
    paged = {}
    for dk, dv in dense.items():
        pool = pools[to_paged[dk]]
        rows = dv.reshape((bt.size, bs) + dv.shape[2:])
        paged[to_paged[dk]] = pool.at[bt.reshape(-1)].set(
            attn.pool_rows(pool, rows, 2))
    return paged


def _padded_gqa_cfg():
    """smollm-360m's smoke model with 5 kv heads of 32: a 160-lane row,
    stored padded to 256 lanes."""
    import dataclasses
    return dataclasses.replace(get_smoke_config("smollm-360m"),
                               num_heads=15, num_kv_heads=5, head_dim=32)


def test_attention_paged_padded_rows_bitwise_equal_dense():
    """With padding lanes in the pool row, paged decode and speculative
    verify still reproduce the dense ring path bit for bit: writes merge
    and pad the new rows, the gather reads the K heads back."""
    from repro.kernels.paged_attention.ops import row_width
    from repro.models import attention as attn

    cfg = _padded_gqa_cfg()
    assert row_width(cfg.num_kv_heads, cfg.head_dim) == 256
    p = attn.init_attention(jax.random.key(1), cfg)
    B, T, bs = 3, 32, 16
    mb = T // bs
    nb = B * mb + 1
    dense = {k: (jax.random.normal(jax.random.fold_in(jax.random.key(3), i),
                                   v.shape, jnp.float32) * 0.1).astype(
                                       v.dtype)
             for i, (k, v) in enumerate(
                 attn.init_kv_cache(cfg, B, T).items())}
    perm = np.random.default_rng(0).permutation(np.arange(1, nb))
    bt = jnp.asarray(perm.reshape(B, mb), jnp.int32)
    paged = _paged_from_dense(cfg, dense, bt, nb, bs)
    assert paged["kp"].shape == (nb, bs, 256)
    x = jax.random.normal(jax.random.key(2), (B, 1, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.asarray([2, 17, 30], jnp.int32)
    out_d, new_d = attn.attention_decode(x, p, cfg, dense, pos)
    out_p, new_p = attn.attention_decode(x, p, cfg, paged, pos,
                                         block_tables=bt)
    np.testing.assert_array_equal(np.asarray(out_d, np.float32),
                                  np.asarray(out_p, np.float32))
    # the written row reads back as the dense ring's, padding left zero
    from repro.kernels.paged_attention.ref import gather_kv
    got = gather_kv(new_p["kp"], bt, (cfg.num_kv_heads, cfg.head_dim))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(new_d["k"], np.float32))
    pad = np.asarray(new_p["kp"][..., cfg.num_kv_heads * cfg.head_dim:],
                     np.float32)
    assert not pad.any()
    # verify: S = 3 queries per row against the same pool
    xs = jax.random.normal(jax.random.key(4), (B, 3, cfg.d_model),
                           jnp.bfloat16)
    pos = jnp.asarray([1, 9, 20], jnp.int32)
    out_v, _ = attn.attention_verify(xs, p, cfg, paged, pos,
                                     block_tables=bt)
    ring = dense
    for s in range(3):
        out_s, ring = attn.attention_decode(xs[:, s:s + 1], p, cfg, ring,
                                            pos + s)
        np.testing.assert_array_equal(np.asarray(out_v[:, s], np.float32),
                                      np.asarray(out_s[:, 0], np.float32))


def test_handoff_keeps_host_format_on_padded_pool():
    """A KV handoff carries each GQA row as (K, Dh), without the pool's
    padding lanes, and importing it resumes the stream bit for bit."""
    cfg = _padded_gqa_cfg()
    params = _params(cfg)
    reqs = [(0, 9, 5), (1, 20, 4)]
    uni = ServeEngine(cfg, params, slots=2, max_len=64)
    pf = ServeEngine(cfg, params, slots=2, max_len=64, role="prefill")
    dc = ServeEngine(cfg, params, slots=2, max_len=64, role="decode")
    for rid, plen, mnt in reqs:
        uni.submit(_req(rid, plen, mnt, cfg.vocab_size))
        pf.submit(_req(rid, plen, mnt, cfg.vocab_size))
    uni.run()
    pf.run()
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    for rid, plen, mnt in reqs:
        h = pf.done[rid].handoff
        for leaf in h.blocks:
            for key in ("kp", "vp"):
                assert leaf[key].shape[1:] == (h.n_prompt_blocks,
                                               pf.block_size, K, Dh)
        assert h.fingerprint == dc.kv_fingerprint()
        req = _req(rid, plen, mnt, cfg.vocab_size)
        req.handoff = h
        dc.submit(req)
    dc.run()
    for rid, _, _ in reqs:
        assert dc.done[rid].tokens == uni.done[rid].tokens
    assert pf.block_leaks() == 0 and dc.block_leaks() == 0


# ---------------------------------------------------------------------------
# engine: paged bitwise == dense across every (decoder) arch family
# ---------------------------------------------------------------------------

def _decoder_archs():
    out = []
    for a in list_archs():
        cfg = get_smoke_config(a)
        if cfg.is_encdec:
            continue                     # paged is a decoder-LM path
        marks = [] if a == "smollm-360m" else [pytest.mark.slow]
        out.append(pytest.param(a, marks=marks))
    return out


@pytest.mark.slow
@pytest.mark.parametrize("arch", _decoder_archs())
def test_engine_paged_tokens_bitwise_equal_dense(arch):
    cfg = get_smoke_config(arch)
    params = _params(cfg)
    reqs = [(7, 6), (20, 4), (4, 8)]

    def run(kv):
        eng = ServeEngine(cfg, params, slots=2, max_len=64, kv=kv)
        for i, (pl, mn) in enumerate(reqs):
            eng.submit(_req(i, pl, mn, cfg.vocab_size))
        stats = eng.run()
        assert stats["completed"] == len(reqs)
        return eng

    engd = run("dense")
    engp = run("paged")
    for i in range(len(reqs)):
        assert engd.done[i].tokens == engp.done[i].tokens, (arch, i)
    cfg = engp.cfg
    if cfg.is_attention_free or (cfg.sliding_window is not None
                                 and cfg.mla is None):
        # nothing to page (pure SSM state / pure rolling rings): the
        # engine must fall back to the dense layout, not run a phantom
        # block pool
        assert engp.kv == "dense" and engp.allocator is None, arch
        return
    assert engp.kv == "paged"
    # eviction returned every request-owned block; only prefix-cache
    # published blocks may remain, and releasing them drains the pool
    if engp.prefix is not None:
        engp.prefix.clear()
    assert engp.allocator.allocated_blocks == 0, arch


# ---------------------------------------------------------------------------
# prefix reuse: copy-free, copy-on-write safe
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_prefix_shared_blocks_are_copy_on_write_safe():
    """Two identical prompts: the second maps the first's full blocks
    copy-free (refcount 2).  While the second request decodes, the shared
    blocks' pool content must stay bit-identical — nothing ever writes at
    or below the shared frontier — and both token streams must match."""
    cfg = get_smoke_config("smollm-360m")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, slots=1, max_len=96, kv="paged")
    prompt = np.arange(2, 2 + 40).astype(np.int32)     # bucket 64
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    eng.run()
    assert eng.prefix is not None and len(eng.prefix) > 0
    hits_before = eng.prefix.hits

    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=5))
    # step once: admission maps shared blocks; snapshot their content
    eng.step()
    assert eng.prefix.hits > hits_before
    shared = [b for b in eng._slot_blocks[0]
              if eng.allocator.refcount(b) > 1]
    assert shared, "second request shares no blocks"

    def pool_bytes():
        out = []
        for leaf in eng.state["cache"]:
            for k, v in leaf.items():
                if k in ("kp", "vp", "ckvp", "kropep"):
                    out.append(np.asarray(v[:, np.asarray(shared)],
                                          np.float32))
        return out

    before = pool_bytes()
    eng.run()
    after = pool_bytes()
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert eng.done[0].tokens == eng.done[1].tokens
    # refcounts fell back to cache-only after eviction
    for b in shared:
        assert eng.allocator.refcount(b) == 1


@pytest.mark.slow
def test_pool_pressure_defers_admission_but_completes():
    """A pool too small for all requests at once must defer admissions
    (blocked_admissions > 0), never deadlock or drop requests."""
    cfg = get_smoke_config("smollm-360m")
    params = _params(cfg)
    # room for ~1.5 worst-case requests at a time (each needs 4 blocks)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv="paged",
                      num_blocks=7, prefix_sharing=False)
    for i in range(4):
        eng.submit(_req(i, 12, 40, cfg.vocab_size))
    stats = eng.run()
    assert stats["completed"] == 4
    assert stats["blocked_admissions"] > 0
    assert eng.allocator.allocated_blocks == 0


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chunked_prefill_isolates_running_slot():
    """A multi-chunk admission must leave the other slot's token stream
    identical to a solo run, and decode must advance between chunks (the
    <=1-chunk interleave rule, not a stop-the-world prefill)."""
    cfg = get_smoke_config("smollm-360m")
    params = _params(cfg)

    solo = ServeEngine(cfg, params, slots=2, max_len=96, kv="paged")
    solo.submit(_req(0, 7, 24, cfg.vocab_size))
    solo.run()

    eng = ServeEngine(cfg, params, slots=2, max_len=96, kv="paged",
                      prefill="chunked", prefill_chunk=16)
    eng.submit(_req(0, 7, 24, cfg.vocab_size))
    for _ in range(3):
        eng.step()
    steps_before = eng.steps
    chunks_before = eng.prefill_chunks
    eng.submit(_req(1, 60, 4, cfg.vocab_size))     # bucket 64 -> 4 chunks
    eng.step()                          # admission starts the chunk job
    while eng._jobs:
        eng.step()
    # every chunk tick also ran a decode step for the busy slot
    assert eng.steps - steps_before >= 4
    assert eng.prefill_chunks - chunks_before == 4
    eng.run()
    assert eng.done[0].tokens == solo.done[0].tokens
    assert eng.done[1].tokens                       # intruder completed


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["mixtral-8x7b", "jamba-v0.1-52b",
                                  "minicpm3-4b"])
def test_chunked_prefill_completes_on_swa_ssm_mla(arch):
    """Chunked admission must work for rolling-window (SWA), SSM-state and
    MLA-latent layers too — their chunk paths write per-row state, not
    paged blocks.  Crucially, a request admitted WHILE another slot
    decodes must produce the same tokens as the same request admitted into
    an idle engine: the batched decode step must not advance a
    mid-admission row's SSM/ring state between chunks (`_guard_rows`)."""
    cfg = get_smoke_config(arch)
    params = _params(cfg)

    solo = ServeEngine(cfg, params, slots=2, max_len=64, kv="paged",
                       prefill="chunked", prefill_chunk=16)
    solo.submit(_req(1, 30, 3, cfg.vocab_size))    # multi-chunk, idle engine
    solo.run()

    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv="paged",
                      prefill="chunked", prefill_chunk=16)
    for i, (pl, mn) in enumerate([(20, 12), (30, 3), (7, 4)]):
        eng.submit(_req(i, pl, mn, cfg.vocab_size))
    stats = eng.run()
    assert stats["completed"] == 3
    assert stats["prefill_chunks"] >= 3
    for i, (pl, mn) in enumerate([(20, 12), (30, 3), (7, 4)]):
        assert len(eng.done[i].tokens) == mn + 1
    # request 1 was admitted chunk-by-chunk while slot 0 decoded; its
    # stream must match the idle-engine run bit for bit
    assert eng.done[1].tokens == solo.done[1].tokens


@pytest.mark.slow
def test_boundary_prompt_max_len_minus_one_serves():
    """A prompt of exactly max_len - 1 tokens is admitted and generates
    its prefill token plus one decode token before max_len eviction."""
    cfg = get_smoke_config("smollm-360m")
    params = _params(cfg)
    for kv in ("dense", "paged"):
        eng = ServeEngine(cfg, params, slots=1, max_len=32, kv=kv)
        eng.submit(_req(0, 31, 50, cfg.vocab_size))
        stats = eng.run()
        assert stats["completed"] == 1, kv
        assert len(eng.done[0].tokens) == 2, (kv, eng.done[0].tokens)


# ---------------------------------------------------------------------------
# stats / telemetry surface
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_stats_report_cache_pressure():
    cfg = get_smoke_config("smollm-360m")
    params = _params(cfg)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv="paged")
    for i in range(3):
        eng.submit(_req(i, 10, 6, cfg.vocab_size))
    eng.step()
    eng.step()
    # kv_pressure is an INSTANTANEOUS sample: with work in flight it shows
    # the current live/allocated ratio (and falls back to 0 once drained)
    press = eng.kv_pressure()
    assert press["kv"] == "paged"
    assert 0.0 < press["kv_memory_utilization"] <= 1.0
    assert press["kv_live_tokens"] > 0
    stats = eng.run()
    assert 0.0 < stats["kv_memory_utilization"] <= 1.0
    assert stats["kv_capacity_tokens"] == eng.allocator.capacity_tokens
    assert stats["kv_peak_live_tokens"] > 0
    assert "prefix_hit_rate" in stats
    assert eng.kv_pressure()["kv_live_tokens"] == 0    # drained
