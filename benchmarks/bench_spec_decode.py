"""Speculative-decoding benchmark: draft-and-verify multi-token steps
against the plain one-token decode loop, on the same trace.

The workload is REPEATED TRAFFIC — a fixed set of hot prompts served
over and over (shared system prompts / repeated queries, the same
regime ``bench_serving``'s prefix-reuse rows model).  The draft is a
narrow decoder DISTILLED on the target's past rollouts of that traffic:
the bench serves the hot set once with the target, teacher-forces the
draft onto those greedy continuations (left-padded exactly as admission
pads them), then times a fresh trace.  This is the production shape of
speculative serving: the drafter is trained on yesterday's traffic and
verified token-by-token against today's target outputs.

Engines under test:

* ``off``       — the PR-2 continuous-batching loop, paged KV, one
  token (and one device->host transfer) per step.  The baseline.
* ``distilled`` — ``spec="draft"`` with the distilled draft.  A draft
  forward is a fraction of the target's, so every accepted token is
  nearly free, and k+1 tokens ride ONE packed transfer + one host
  scheduling pass.  Headline row (target >= 1.5x tok/s).
* ``mixed``     — the distilled draft on traffic diluted with novel
  prompts the draft has never seen: acceptance collapses on the novel
  slots, which decode one token per step and hog the step budget —
  the honest picture of how spec decoding degrades off-distribution.
* ``self``      — the target drafting for itself (ablation): acceptance
  is as high as numerics allow but each draft token costs a full target
  forward, so this isolates transfer/host amortization with zero
  compute savings.
* ``random``    — an untrained draft (ablation): near-zero acceptance
  shows the misprediction floor — the verify forward always commits at
  least one target token per step, so ``tokens_per_step`` never drops
  below the plain loop's.

Token streams from every spec engine must be bitwise identical to the
baseline; the bench RAISES on mismatch, on a broken one-transfer
invariant (``d2h_transfers != decode_steps``), and on leaked blocks
after the trace drains (speculation must not allocate).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.models import transformer as tf
from repro.models.api import build_model
from repro.optim.adamw import OptimConfig, adamw_update, init_opt_state
from repro.serving.engine import ServeEngine, admit_length

MAX_LEN = 96
N_HOT = 8
ROLLOUT_BUDGET = 60       # past-traffic budget; eval budgets stay below
DISTILL_STEPS = 500


def _bench_config(arch: str):
    """The smoke configs are deliberately tiny (d_model 60) — at that
    size a draft forward costs nearly as much as a target forward and
    speculation can only amortize host overhead.  Scale the target so
    the draft/target compute gap is the one any real deployment has."""
    cfg = get_smoke_config(arch)
    return dataclasses.replace(
        cfg, name=cfg.name + "-spec-bench", d_model=256, num_heads=4,
        num_kv_heads=2, head_dim=64, d_ff=512, vocab_size=2048)


def _draft_config(cfg):
    """A draft a fraction of the target's width and depth.  Must share
    the target's vocab (verify compares argmax ids directly)."""
    return dataclasses.replace(
        cfg, name=cfg.name + "-draft", d_model=32, num_heads=2,
        num_kv_heads=1, head_dim=16, d_ff=64, num_layers=2)


def _hot_prompts(cfg):
    rng = np.random.default_rng(42)
    return [rng.integers(0, cfg.vocab_size,
                         size=int(rng.integers(8, 25))).tolist()
            for _ in range(N_HOT)]


def _distill(cfg, params, dcfg, hot, *, slots):
    """Serve the hot set once (past traffic), then teacher-force the
    draft onto the target's greedy continuations.  Prompts are
    left-padded to their admit bucket, exactly as the engine pads them
    at admission — the draft must see the contexts it will serve."""
    trace = [{"rid": i, "prompt": p, "max_new_tokens": ROLLOUT_BUDGET,
              "at_step": i} for i, p in enumerate(hot)]
    eng = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                      kv="paged")
    eng.run_trace(trace)
    seqs = [(hot[i], list(eng.done[i].tokens)) for i in eng.done]

    L = MAX_LEN - 1
    toks = np.zeros((len(seqs), L), np.int32)
    tgts = np.zeros((len(seqs), L), np.int32)
    mask = np.zeros((len(seqs), L), np.float32)
    for i, (p, c) in enumerate(seqs):
        plen = admit_length(len(p), MAX_LEN)
        full = ([0] * (plen - len(p)) + p + c)[:L + 1]
        toks[i, :len(full) - 1] = full[:-1]
        tgts[i, :len(full) - 1] = full[1:]
        mask[i, plen - 1:len(full) - 1] = 1.0

    def loss_fn(p, b):
        l, _ = tf.lm_loss(p, dcfg, b["tokens"], b["targets"],
                          loss_mask=b["mask"], compute=jnp.float32)
        return l

    oc = OptimConfig(peak_lr=3e-3, warmup_steps=50,
                     total_steps=DISTILL_STEPS, weight_decay=0.0)

    @jax.jit
    def train_step(p, opt, b):
        l, g = jax.value_and_grad(loss_fn)(p, b)
        p, opt, _ = adamw_update(p, g, opt, oc)
        return p, opt, l

    dparams = build_model(dcfg).init(jax.random.key(1))
    opt = init_opt_state(dparams)
    batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(tgts),
             "mask": jnp.asarray(mask)}
    t0 = time.monotonic()
    for _ in range(DISTILL_STEPS):
        dparams, opt, l = train_step(dparams, opt, batch)
    return dparams, float(l), time.monotonic() - t0, len(seqs)


def _repeat_trace(hot, n=16, seed=0):
    r = np.random.default_rng(seed)
    return [{"rid": i, "prompt": list(hot[int(r.integers(len(hot)))]),
             "max_new_tokens": int(r.choice([40, 48, 56])), "at_step": i}
            for i in range(n)]


def _mixed_trace(cfg, hot, n=18, seed=1):
    """2/3 repeated traffic, 1/3 prompts the draft has never seen."""
    r = np.random.default_rng(seed)
    out = []
    for i in range(n):
        if i % 3 < 2:
            prompt = list(hot[int(r.integers(len(hot)))])
        else:
            prompt = r.integers(0, cfg.vocab_size,
                                size=int(r.integers(4, 20))).tolist()
        out.append({"rid": i, "prompt": prompt,
                    "max_new_tokens": int(r.choice([16, 24, 32])),
                    "at_step": i})
    return out


def _tokens_by_rid(eng) -> dict:
    return {rid: tuple(r.tokens) for rid, r in eng.done.items()}


def _assert_invariants(eng, stats, base_tokens, label):
    if _tokens_by_rid(eng) != base_tokens:
        bad = [r for r in base_tokens
               if _tokens_by_rid(eng).get(r) != base_tokens[r]]
        raise RuntimeError(
            f"spec-vs-off token mismatch ({label}): rids {bad[:4]}")
    if stats["d2h_transfers"] != stats["decode_steps"]:
        raise RuntimeError(
            f"one-transfer invariant broken ({label}): "
            f"{stats['d2h_transfers']} transfers over "
            f"{stats['decode_steps']} steps")
    # speculation must not allocate: after the trace drains, the only
    # live blocks are prefix-cache pins, and flushing those frees all
    if eng.allocator is not None:
        if eng.allocator.allocated_blocks != len(eng.prefix._map):
            raise RuntimeError(
                f"block leak ({label}): {eng.allocator.allocated_blocks} "
                f"allocated vs {len(eng.prefix._map)} prefix pins")
        eng.prefix.evict_unreferenced(eng.allocator.capacity_blocks)
        if eng.allocator.allocated_blocks != 0:
            raise RuntimeError(f"block leak after flush ({label})")


_WARM_TRACE = [{"rid": 900 + i, "prompt": list(range(2, 2 + n)),
                "max_new_tokens": 4, "at_step": 0}
               for i, n in enumerate((6, 20))]


def _timed_run(eng, trace):
    """Warm every admit bucket AND the (spec) step functions before the
    timed region — a cold draft/verify jit would otherwise be billed to
    the first measured step."""
    eng.warm_admission()
    eng.run_trace([dict(e) for e in _WARM_TRACE])
    eng.reset_metrics()
    return eng.run_trace(trace)


def _spec_engine(cfg, params, *, slots, spec_k, draft=None):
    kw = {}
    if draft is not None:
        kw["draft_cfg"], kw["draft_params"] = draft
    return ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="paged", spec="draft", spec_k=spec_k, **kw)


def run(arch: str = "smollm-360m", slots: int = 4,
        spec_k: int = 8) -> list[tuple[str, float, str]]:
    cfg = _bench_config(arch)
    params = build_model(cfg).init(jax.random.key(0))
    dcfg = _draft_config(cfg)
    hot = _hot_prompts(cfg)
    dparams, dloss, dtrain_s, nseq = _distill(cfg, params, dcfg, hot,
                                              slots=slots)
    rep = _repeat_trace(hot)
    mix = _mixed_trace(cfg, hot)

    engo = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="paged")
    off = _timed_run(engo, rep)
    base = _tokens_by_rid(engo)

    engo_m = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                         kv="paged")
    off_m = _timed_run(engo_m, mix)
    base_m = _tokens_by_rid(engo_m)

    def spec_run(label, trace, base_tokens, off_stats, *, k, draft):
        eng = _spec_engine(cfg, params, slots=slots, spec_k=k,
                           draft=draft)
        stats = _timed_run(eng, trace)
        _assert_invariants(eng, stats, base_tokens, label)
        stats["ratio"] = (stats["tok_per_s"] / off_stats["tok_per_s"]
                          if off_stats["tok_per_s"] else float("inf"))
        return stats

    dist = spec_run("distilled", rep, base, off, k=spec_k,
                    draft=(dcfg, dparams))
    dist_lo = spec_run("distilled-lo", rep, base, off,
                       k=max(2, spec_k // 2), draft=(dcfg, dparams))
    mixed = spec_run("mixed", mix, base_m, off_m, k=spec_k,
                     draft=(dcfg, dparams))
    slf = spec_run("self-draft", rep, base, off, k=4, draft=None)
    rnd = spec_run("random-draft", rep, base, off, k=spec_k,
                   draft=(dcfg, build_model(dcfg).init(jax.random.key(7))))

    detail = f"{arch} scaled, {slots} slots, k={spec_k}, repeated traffic"
    return [
        ("spec_tok_per_s", dist["tok_per_s"],
         detail + " (distilled draft)"),
        ("spec_off_tok_per_s", off["tok_per_s"], detail + " (spec off)"),
        ("spec_vs_off_tok_ratio", dist["ratio"],
         "distilled draft / off tok/s (target >= 1.5, tokens bitwise "
         "equal)"),
        ("spec_acceptance_rate", dist["acceptance_rate"],
         f"accepted / drafted; distilled on {nseq} past rollouts, "
         f"final CE {dloss:.2g}"),
        ("spec_tokens_per_step", dist["tokens_per_step"],
         "committed tokens per decode step (1 per live slot when off)"),
        ("spec_decode_steps", float(dist["decode_steps"]),
         f"vs {off['decode_steps']} steps with spec off"),
        ("spec_d2h_per_step",
         dist["d2h_transfers"] / dist["decode_steps"]
         if dist["decode_steps"] else 0.0,
         "device->host transfers per step (must be 1; k+1 tokens ride "
         "it)"),
        ("spec_distill_train_s", dtrain_s,
         f"{DISTILL_STEPS} teacher-forced steps, one-time cost"),
        ("spec_token_match", 1.0,
         "every spec engine bitwise == off (raises otherwise)"),
        ("spec_k_half_tok_ratio", dist_lo["ratio"],
         f"distilled draft at k={max(2, spec_k // 2)}"),
        ("spec_mixed_tok_ratio", mixed["ratio"],
         "1/3 novel prompts: novel slots decode 1 tok/step and dilute "
         "the win"),
        ("spec_mixed_acceptance", mixed["acceptance_rate"],
         "acceptance under off-distribution dilution"),
        ("spec_self_draft_tok_ratio", slf["ratio"],
         "self-draft k=4: transfer amortization only, each draft token "
         "costs a full target forward"),
        ("spec_self_draft_acceptance", slf["acceptance_rate"],
         "acceptance ceiling (limited only by S=1 vs S=k+1 numerics)"),
        ("spec_random_draft_acceptance", rnd["acceptance_rate"],
         "untrained draft: the acceptance floor"),
        ("spec_random_draft_tokens_per_step", rnd["tokens_per_step"],
         "never below 1/slot: verify always commits one target token"),
    ]


def run_smoke(arch: str = "smollm-360m") -> list[tuple[str, float, str]]:
    """CI smoke: a short trace through spec="draft" (self-draft — no
    training in CI) and the baseline; RAISES on token mismatch,
    acceptance_rate == 0, a broken one-transfer invariant, or leaked
    blocks after the trace drains."""
    from repro.launch.serve import make_trace
    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.key(0))
    trace = make_trace(cfg.vocab_size, 6, max_len=MAX_LEN, stagger=2,
                       seed=3)

    engo = ServeEngine(cfg, params, slots=2, max_len=MAX_LEN, kv="paged")
    off = engo.run_trace(trace)
    base = _tokens_by_rid(engo)

    engs = ServeEngine(cfg, params, slots=2, max_len=MAX_LEN, kv="paged",
                       spec="draft", spec_k=4)
    spec = engs.run_trace([dict(e) for e in trace])
    _assert_invariants(engs, spec, base, "smoke self-draft")
    if spec["acceptance_rate"] <= 0.0:
        raise RuntimeError("smoke acceptance_rate is zero — the draft "
                           "scan or the verify accept mask is broken")
    return [
        ("spec_smoke_token_match", 1.0,
         "spec bitwise == off on the smoke trace"),
        ("spec_smoke_acceptance_rate", spec["acceptance_rate"],
         "self-draft, must be > 0"),
        ("spec_smoke_tokens_per_step", spec["tokens_per_step"],
         f"vs 1/slot over {off['decode_steps']} baseline steps"),
        ("spec_smoke_d2h_per_step",
         spec["d2h_transfers"] / spec["decode_steps"]
         if spec["decode_steps"] else 0.0,
         "one packed transfer per step"),
        ("spec_smoke_completed", float(spec["completed"]), "of 6"),
    ]
