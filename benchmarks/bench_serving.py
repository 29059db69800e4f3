"""Serving-engine benchmark: paged KV vs the dense slab, chunked vs
stop-the-world prefill, and the continuous-batching loop vs the seed wave
engine — all on staggered-arrival workloads with mixed token budgets.

Engines under test:

* ``paged``   — the default serve path: block-pool KV + block tables,
  lazy allocation, prefix reuse.  Its pool is sized to the TRACE's worst
  case, not to slots x max_len, so the capacity rows measure how many
  concurrent admitted tokens each HBM byte actually carries.
* ``dense``   — the (slots, max_len) slab ablation (``kv="dense"``).
  Paged decode must produce bitwise-identical token streams; the bench
  RAISES on mismatch (CI runs it as a smoke).
* ``chunked`` — paged + chunked admission prefill on a long-prompt trace,
  against the same engine with stop-the-world (one-shot) admission: the
  p99 per-output-token latency shows decode stalls disappearing.
* ``wave``    — a faithful replica of the seed engine (wave-scheduled
  admission, per-wave state reallocation, per-slot host syncs) and
  ``barrier`` (new loop, wave admission) bracket the PR-2 win.

All engines run the SAME trace with the same params; each is jit-warmed
on a side trace first so the numbers measure steady-state serving, not
compile time.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.launch.serve import make_trace
from repro.models.api import build_model, init_decode_state
from repro.serving.engine import (
    Request, ServeEngine, _install_slot, admit_length)

MAX_LEN = 96
BLOCK = 16


class _SeedWaveEngine:
    """The seed's wave-scheduled engine, kept here as the benchmark
    baseline: all slots are refilled together once the LAST request of the
    wave drains; `pos` is one scalar shared by the wave; every step pays an
    argmax host round-trip plus an ``int(pos)`` sync per live slot."""

    def __init__(self, cfg, params, *, slots: int, max_len: int):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.bundle = build_model(cfg)
        self.state = init_decode_state(cfg, slots, max_len)
        self.meta = [[-1, 0] for _ in range(slots)]      # [rid, remaining]
        self.queue: list[Request] = []
        self.done: dict[int, Request] = {}
        self._live: dict[int, Request] = {}
        self.steps = 0
        self._decode = jax.jit(self.bundle.decode, donate_argnums=1)
        self._prefill = {}

    def submit(self, req: Request):
        self.queue.append(req)

    def _bucket(self, n):
        b = 16
        while b < n:
            b *= 2
        return min(b, self.max_len - 1)

    def _start_wave(self):
        wave, self.queue = self.queue[:self.slots], self.queue[self.slots:]
        if not wave:
            return
        plen = max(self._bucket(len(r.prompt)) for r in wave)
        self.state = init_decode_state(self.cfg, self.slots, self.max_len)
        for si, req in enumerate(wave):
            toks = np.zeros((1, plen), np.int32)
            toks[0, -len(req.prompt):] = req.prompt
            fn = self._prefill.setdefault(plen, jax.jit(
                lambda p, b: self.bundle.prefill(p, b)))
            logits, cache = fn(self.params, {"tokens": jnp.asarray(toks)})
            nxt = int(jnp.argmax(logits[0, -1]))         # per-request sync
            self.state = _install_slot(self.state, cache, si, plen, nxt)
            self.meta[si] = [req.rid, req.max_new_tokens]
            req.tokens.append(nxt)
            req.first_token_s = time.monotonic() - req.submitted
            self._live[req.rid] = req
        self.state = {**self.state, "pos": jnp.asarray(plen, jnp.int32)}

    def step(self) -> int:
        live = [m for m in self.meta if m[0] != -1]
        if not live:
            self._start_wave()
            live = [m for m in self.meta if m[0] != -1]
            if not live:
                return 0
        logits, self.state = self._decode(self.params, self.state)
        self.steps += 1
        toks = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        for si, m in enumerate(self.meta):
            if m[0] == -1:
                continue
            req = self._live[m[0]]
            req.tokens.append(int(toks[si]))
            m[1] -= 1
            if m[1] <= 0 or int(self.state["pos"]) >= self.max_len - 1:
                req.done_s = time.monotonic() - req.submitted
                self.done[req.rid] = req
                del self._live[m[0]]
                m[0] = -1
        return len(live)


def _drive(eng, trace) -> dict:
    """Tick-driven trace loop (staggered arrivals), shared with the seed
    wave engine (which predates run_trace)."""
    pending = sorted(trace, key=lambda e: e["at_step"])
    t0 = time.monotonic()
    decoded, tick, i = 0, 0, 0
    while i < len(pending) or eng.queue or eng._live:
        while i < len(pending) and pending[i]["at_step"] <= tick:
            e = pending[i]
            i += 1
            eng.submit(Request(rid=e["rid"],
                               prompt=np.asarray(e["prompt"], np.int32),
                               max_new_tokens=e["max_new_tokens"]))
        decoded += eng.step()
        tick += 1
    wall = time.monotonic() - t0
    util = decoded / (eng.steps * eng.slots) if eng.steps else 0.0
    return {"tok_per_s": decoded / wall if wall else 0.0,
            "slot_utilization": util, "completed": len(eng.done)}


def _trace_pool_blocks(trace, slots: int, max_len: int, bs: int) -> int:
    """Smallest pool that can hold `slots` concurrent worst-case requests
    of this trace (what a demand-shaped deployment would provision)."""
    worst = max(-(-min(admit_length(len(e["prompt"]), max_len)
                       + e["max_new_tokens"], max_len) // bs)
                for e in trace)
    return slots * worst + 1                     # + scratch block


def _tokens_by_rid(eng) -> dict:
    return {rid: tuple(r.tokens) for rid, r in eng.done.items()}


def _assert_token_match(a, b, label):
    ta, tb = _tokens_by_rid(a), _tokens_by_rid(b)
    if ta != tb:
        bad = [r for r in ta if ta.get(r) != tb.get(r)]
        raise RuntimeError(
            f"dense-vs-paged output mismatch ({label}): rids {bad[:4]}")


def _prefix_trace(vocab: int, n: int, max_len: int, seed: int = 5):
    """Half the requests repeat one LONG prompt (a shared system prompt /
    repeated query): its full blocks below the tail are mapped copy-free.
    Short (bucket-16) prompts can never share — their single block holds
    the last prompt position, which admission must recompute."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, vocab, size=min(40, max_len - 8)).tolist()
    trace = []
    for i in range(n):
        if i % 2:
            prompt = list(base)
        else:
            prompt = rng.integers(0, vocab,
                                  size=int(rng.integers(4, 20))).tolist()
        trace.append({
            "rid": i,
            "prompt": prompt,
            "max_new_tokens": int(rng.choice([4, 8, 12])),
            "at_step": i,
        })
    return trace


def _long_mix_trace(vocab: int, n: int, max_len: int, seed: int = 7):
    """Short decodes punctuated by LONG prompts: the workload where a
    stop-the-world admission stalls every running slot."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n):
        if i % 4 == 2:
            plen = int(rng.integers(60, max_len - 2))    # bucket 64 / 95
        else:
            plen = int(rng.integers(4, 20))
        trace.append({
            "rid": i,
            "prompt": rng.integers(0, vocab, size=plen).tolist(),
            "max_new_tokens": int(rng.choice([8, 12, 20])),
            "at_step": i * 2,
        })
    return trace


def _bench_config(arch: str):
    """The smoke configs are deliberately tiny (d_model 60) — at that size
    cache plumbing, not matmuls, dominates a decode step and every engine
    comparison measures dispatch overhead.  Scale the model so the decode
    math is the cost, as it is in any real deployment."""
    cfg = get_smoke_config(arch)
    return dataclasses.replace(
        cfg, name=cfg.name + "-bench", d_model=256, num_heads=4,
        num_kv_heads=2, head_dim=64, d_ff=512, vocab_size=2048)


def run(arch: str = "smollm-360m", n_requests: int = 32,
        slots: int = 4) -> list[tuple[str, float, str]]:
    cfg = _bench_config(arch)
    params = build_model(cfg).init(jax.random.key(0))
    trace = make_trace(cfg.vocab_size, n_requests, max_len=MAX_LEN,
                       stagger=1, seed=0)
    dup_trace = _prefix_trace(cfg.vocab_size, n_requests, MAX_LEN)
    # warm both prefill buckets (16 and 32) IN SEPARATE WAVES so the seed
    # baseline also compiles each plen before the timed run — its wave
    # admission pads a joint wave to the larger bucket, which would leave
    # the small bucket's compile inside the measured region
    warm = [{"rid": 1000 + i, "prompt": list(range(2, 2 + n)),
             "max_new_tokens": 2, "at_step": i * 8}
            for i, n in enumerate((6, 20))]

    # paged engine: pool sized to the trace (the demand-shaped claim), not
    # to slots x max_len
    pool_blocks = _trace_pool_blocks(trace, slots, MAX_LEN, BLOCK)
    engp = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="paged", num_blocks=pool_blocks)
    engp.run_trace(warm)
    engp.reset_metrics()
    paged = engp.run_trace(trace)

    # dense slab ablation (identical trace; token streams must match)
    engd = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="dense")
    engd.run_trace(warm)
    engd.reset_metrics()
    dense = engd.run_trace(trace)
    _assert_token_match(engd, engp, "staggered trace")

    # prefix reuse: repeated-prompt trace on the paged engine
    prefix = None
    if engp.prefix is not None:
        engp.reset_metrics()
        prefix = engp.run_trace(dup_trace)

    # chunked vs stop-the-world admission on the long-prompt mix
    long_trace = _long_mix_trace(cfg.vocab_size, max(8, n_requests // 2),
                                 MAX_LEN)
    long_warm = [{"rid": 2000 + i, "prompt": list(range(2, 2 + n)),
                  "max_new_tokens": 2, "at_step": i * 10}
                 for i, n in enumerate((6, 20, 60, MAX_LEN - 2))]
    engc = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="paged", prefill="chunked", prefill_chunk=32)
    engc.warm_admission()                 # stage EVERY chunk shape
    engc.run_trace(long_warm)
    engc.reset_metrics()
    chunked = engc.run_trace(long_trace)
    engo = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="paged", prefill="oneshot")
    engo.run_trace(long_warm)
    engo.reset_metrics()
    oneshot = engo.run_trace(long_trace)

    # degraded-admission variant of the new loop (isolates admission policy)
    engb = ServeEngine(cfg, params, slots=slots, max_len=MAX_LEN,
                       kv="dense", admission="wave")
    engb.run_trace(warm)
    engb.reset_metrics()
    barrier = engb.run_trace(trace)

    # the seed wave engine (what PR 2 replaced)
    wv = _SeedWaveEngine(cfg, params, slots=slots, max_len=MAX_LEN)
    _drive(wv, warm)
    wv.steps = 0
    wv.done.clear()
    wave = _drive(wv, trace)

    detail = f"{arch}, {slots} slots, {n_requests} staggered reqs"
    d2h_per_step = (paged["d2h_transfers"] / paged["decode_steps"]
                    if paged["decode_steps"] else 0.0)
    # effective cache capacity: concurrent admitted tokens per token of
    # allocated HBM (higher = each byte of claim carries more traffic)
    eff_p = paged["kv_peak_live_tokens"] / paged["kv_capacity_tokens"]
    eff_d = dense["kv_peak_live_tokens"] / dense["kv_capacity_tokens"]
    rows = [
        ("serve_tok_per_s", paged["tok_per_s"], detail + " (paged)"),
        ("serve_slot_utilization", paged["slot_utilization"],
         "continuous batching, paged KV"),
        ("serve_ttft_p50_s", paged["ttft_p50_s"], detail),
        ("serve_ttft_p99_s", paged["ttft_p99_s"], detail),
        ("serve_tpot_p50_s", paged["tpot_p50_s"], "per-output-token latency"),
        ("serve_tpot_p99_s", paged["tpot_p99_s"], "per-output-token latency"),
        ("serve_d2h_per_step", d2h_per_step,
         "device->host transfers per decode step (must be 1)"),
        ("serve_completed", float(paged["completed"]), f"of {n_requests}"),
        ("serve_paged_token_match", 1.0,
         "paged token streams bitwise == dense (raises otherwise)"),
        ("serve_dense_tok_per_s", dense["tok_per_s"], "dense slab ablation"),
        ("serve_paged_vs_dense_tok_ratio",
         paged["tok_per_s"] / dense["tok_per_s"] if dense["tok_per_s"]
         else float("inf"), "must stay ~1 (capacity is the win, not speed)"),
        ("serve_paged_capacity_tokens", float(paged["kv_capacity_tokens"]),
         f"pool {pool_blocks} blocks x {BLOCK}"),
        ("serve_dense_capacity_tokens", float(dense["kv_capacity_tokens"]),
         f"slab {slots} x {MAX_LEN}"),
        ("serve_paged_eff_capacity", eff_p,
         "peak concurrent admitted tokens / cache capacity tokens"),
        ("serve_dense_eff_capacity", eff_d,
         "peak concurrent admitted tokens / cache capacity tokens"),
        ("serve_paged_capacity_gain", eff_p / eff_d if eff_d else float("inf"),
         "paged / dense effective capacity (target >= 1.3 at equal tok/s)"),
        ("serve_kv_mem_util_paged", paged["kv_memory_utilization"],
         "live tokens / ALLOCATED tokens, mean over steps"),
        ("serve_kv_mem_util_dense", dense["kv_memory_utilization"],
         "live tokens / allocated tokens (slab allocates everything)"),
        ("serve_chunked_tpot_p99_s", chunked["tpot_p99_s"],
         "chunked prefill, long-prompt mix"),
        ("serve_oneshot_tpot_p99_s", oneshot["tpot_p99_s"],
         "stop-the-world prefill, long-prompt mix"),
        ("serve_chunked_prefill_chunks", float(chunked["prefill_chunks"]),
         "admission chunks interleaved with decode"),
        ("serve_wave_tok_per_s", wave["tok_per_s"], "seed wave engine"),
        ("serve_wave_slot_utilization", wave["slot_utilization"],
         "seed wave engine"),
        ("serve_speedup_vs_wave", paged["tok_per_s"] / wave["tok_per_s"]
         if wave["tok_per_s"] else float("inf"),
         "paged continuous / seed wave tok/s"),
        ("serve_barrier_tok_per_s", barrier["tok_per_s"],
         "new loop, wave admission (policy ablation)"),
    ]
    if prefix is not None:
        rows += [
            ("serve_prefix_hit_rate", prefix["prefix_hit_rate"],
             "50% repeated prompts: fraction of prompt tokens mapped "
             "copy-free"),
            ("serve_prefix_kv_mem_util", prefix["kv_memory_utilization"],
             "live / allocated under prefix sharing"),
        ]
    return rows


def run_smoke(arch: str = "smollm-360m") -> list[tuple[str, float, str]]:
    """CI smoke: `bench_serving --kv paged --smoke` — a small staggered
    trace through the paged AND dense engines; RAISES on any dense-vs-paged
    token-stream mismatch."""
    cfg = get_smoke_config(arch)
    params = build_model(cfg).init(jax.random.key(0))
    # half the prompts repeat one bucket-64 prompt, so prefix reuse fires
    # (bucket-16 prompts have no shareable full block below their tail)
    trace = _prefix_trace(cfg.vocab_size, 8, 96, seed=2)
    engp = ServeEngine(cfg, params, slots=2, max_len=96, kv="paged")
    paged = engp.run_trace(trace)
    engd = ServeEngine(cfg, params, slots=2, max_len=96, kv="dense")
    dense = engd.run_trace(trace)
    _assert_token_match(engd, engp, "smoke trace")
    engc = ServeEngine(cfg, params, slots=2, max_len=96, kv="paged",
                       prefill="chunked", prefill_chunk=16)
    chunked = engc.run_trace(trace)
    if chunked["completed"] != paged["completed"]:
        raise RuntimeError("chunked prefill dropped requests: "
                           f"{chunked['completed']} != {paged['completed']}")
    return [
        ("serve_smoke_paged_token_match", 1.0,
         "paged bitwise == dense on the smoke trace"),
        ("serve_smoke_completed", float(paged["completed"]), "of 8"),
        ("serve_smoke_prefix_hit_rate", paged["prefix_hit_rate"],
         "50% repeated long prompts"),
        ("serve_smoke_chunked_chunks", float(chunked["prefill_chunks"]),
         "chunked admission ran"),
        ("serve_smoke_kv_mem_util", paged["kv_memory_utilization"],
         "live / allocated tokens"),
    ]
