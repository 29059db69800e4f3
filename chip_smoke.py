"""Chip smoke: the serve path end to end on a TPU, at published widths.

  python chip_smoke.py              # one chip: kernels, then pilot serve
  python chip_smoke.py --chips 4    # four chips: tensor-parallel serve only

One chip (smollm-360m: 32 layers, d_model 960, 15 heads / 5 KV heads,
d_ff 2560, vocab 49152, tied; random weights from ``--seed``):

1. kernels — flash prefill, paged decode and paged verify run compiled
   (``interpret=False``) and are compared on the device with their
   ``ref.py`` oracles under written tolerances; so is the dropless MoE
   layer at granite-moe-3b-a800m widths (grouped-matmul kernel) against
   per-token dense gating, with its share of padding rows; the engine's
   lowered decode step must contain ``tpu_custom_call`` (a Pallas
   kernel), so a kernel cannot silently have become a reference; the
   decode step of
   the 32x2048 image, compiled for this chip, must hold no ``copy`` shaped
   like a KV pool (a layer's slice or all layers), so the paged kernel
   reads the pools in the layout the device stores them in; and the staged
   weights init a bind dispatches must match the eager init (layers
   bitwise, the rest within one ulp), and at granite-moe-3b-a800m's 16
   layers hold at most ``INIT_TEMP_LIMIT`` bytes of temp.
2. pilot — ``ClusterSim`` -> ``TaskRepo.submit(PayloadImage(...,
   smoke=False, flags=(("attn_impl", "pallas"),)))`` -> pilot ->
   ``run_wrapper`` -> ``ServeEngine`` (paged KV, Pallas prefill + paged
   decode) answers a few requests.  Fails unless the payload exits 0 with
   no error, every request gets its full budget, every token is in vocab
   and the engine made one device->host transfer per decode step.

``--chips 4`` serves granite-moe-3b-a800m at full width (depth cut to fit
one chip whole in f32) once on a ``(1, 4)`` serve mesh and once on one
device, both through the pilot path in this one process, and compares
their greedy tokens and per-device KV bytes.  Divergence is reported, not
hidden behind a tolerance; it fails only if a payload fails.

Lines before the last print smoke figures (compile seconds, TTFT, tok/s):
they are not benchmark metrics.  The last line is one JSON object naming
the device.  Without a TPU, or without the repo's ``src/`` next to this
file, it exits non-zero and prints no result.  One process owns the
chip: nothing here starts a child process.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

SERVE_ARCH = "smollm-360m"
SLOTS, MAX_LEN = 8, 1024
N_REQUESTS = 8
PROMPT_LEN = (64, 512)            # inclusive bounds
BUDGET = (16, 64)

POOL_SLOTS, POOL_MAX_LEN = 32, 2048   # the benchmark's big serve image

MOE_ARCH, MOE_TOKENS = "granite-moe-3b-a800m", 2048   # one MoE layer

# the staged weights init of granite-moe-3b-a800m at the benchmark's 16
# layers, compiled for this chip: most temp bytes it may hold (it makes one
# layer group at a time; a vmap over the layers needs 10.7 GB on the CPU)
INIT_TEMP_LIMIT = 2.5e9

TP_ARCH = "granite-moe-3b-a800m"
TP_LAYERS = 16                    # of 32: f32 weights of the full depth do
#                                   not fit one v5e chip's 15.75 GB
TP_MESH = (1, 4)                  # 24 heads / 8 KV heads split 4 ways
TP_SLOTS, TP_MAX_LEN = 4, 256
TP_REQUESTS = 4
TP_PROMPT_LEN, TP_BUDGET = (33, 64), (16, 32)

# kernel vs f32 oracle: the kernels feed the MXU bf16 operands with f32
# accumulation and emit bf16, so allow |err| <= ATOL + RTOL * |ref| (the
# interpret-mode tests use the same bound)
ATOL, RTOL = 2e-2, 5e-2


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Backend compile seconds, summed from JAX's own compile-duration
    events (compiles run on the pilot's payload thread, hence the lock)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import threading
        import jax.monitoring
        self._lock = threading.Lock()
        self._n, self._s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            with self._lock:
                self._n += 1
                self._s += duration

    def lap(self, phase: str) -> None:
        """Print and reset the compiles since the last lap."""
        with self._lock:
            n, s = self._n, self._s
            self._n, self._s = 0, 0.0
        say(f"{phase}: {n} backend compiles, {s!r} s compiling")


def require_tpu(n_chips: int):
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found platform {platform!r}")
    devices = jax.devices()
    if len(devices) < n_chips:
        raise SmokeFailure(f"needs {n_chips} chips; JAX found "
                           f"{len(devices)}")
    return devices


def import_repo() -> None:
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SmokeFailure(f"repo sources not found at {src}")
    sys.path.insert(0, str(src))


# --------------------------------------------------------------------------
# phase 1: kernels at smollm-360m widths
# --------------------------------------------------------------------------

def _check_close(name: str, out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    if out.shape != ref.shape:
        raise SmokeFailure(f"{name}: shape {out.shape} != ref {ref.shape}")
    err = np.abs(out - ref)
    bound = ATOL + RTOL * np.abs(ref)
    max_err = float(err.max())
    ok = bool(np.isfinite(out).all() and (err <= bound).all())
    say(f"kernel {name}: max |kernel - ref| = {max_err!r} "
        f"(tolerance {ATOL} + {RTOL}*|ref|) {'ok' if ok else 'FAIL'}")
    if not ok:
        raise SmokeFailure(f"{name} departs from its reference")
    return max_err


def _timed(fn, *args):
    """(result, first-call seconds, second-call seconds): the first call
    compiles, so their difference is the compile time."""
    import jax
    t0 = time.monotonic()
    out = jax.block_until_ready(fn(*args))
    t1 = time.monotonic()
    jax.block_until_ready(fn(*args))
    return out, t1 - t0, time.monotonic() - t1


def kernel_phase(cfg, seed: int, *, interpret: bool = False) -> dict:
    """Main-path kernels at ``cfg``'s widths against their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention.ops import (
        paged_decode_attention, paged_verify_attention, row_width)
    from repro.kernels.paged_attention.ref import (
        paged_decode_attention_ref, paged_verify_attention_ref)

    H, K, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs, mb = 16, MAX_LEN // 16
    nb = SLOTS * mb + 1
    ks = jax.random.split(jax.random.key(seed), 8)
    normal = lambda k, shape: jax.random.normal(k, shape, jnp.bfloat16)

    q = normal(ks[0], (1, 512, H, Dh))
    k = normal(ks[1], (1, 512, K, Dh))
    v = normal(ks[2], (1, 512, K, Dh))
    kp = normal(ks[3], (nb, bs, row_width(K, Dh)))
    vp = normal(ks[4], (nb, bs, row_width(K, Dh)))
    rng = np.random.default_rng(seed)
    # every slot owns its own physical blocks, in scrambled order
    tables = jnp.asarray(1 + rng.permutation(SLOTS * mb).reshape(SLOTS, mb),
                         jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_LEN + 1, size=SLOTS), jnp.int32)
    qd = normal(ks[5], (SLOTS, H, Dh))
    S = 5                                       # spec_k 4 + 1
    qv = normal(ks[6], (SLOTS, S, H, Dh))
    off = jnp.asarray(rng.integers(0, MAX_LEN - S + 1, size=SLOTS),
                      jnp.int32)

    cases = {
        "flash_prefill": (
            lambda *a: flash_attention(*a, interpret=interpret),
            lambda *a: attention_ref(*a), (q, k, v)),
        "paged_decode": (
            lambda *a: paged_decode_attention(*a, kv_heads=K,
                                              interpret=interpret),
            lambda *a: paged_decode_attention_ref(*a, kv_heads=K),
            (qd, kp, vp, tables, lens)),
        "paged_verify": (
            lambda *a: paged_verify_attention(*a, kv_heads=K,
                                              interpret=interpret),
            lambda *a: paged_verify_attention_ref(*a, kv_heads=K),
            (qv, kp, vp, tables, off)),
    }
    out = {}
    for name, (kernel, ref, args) in cases.items():
        got, first, steady = _timed(jax.jit(kernel), *args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        out[name] = {"max_abs_err": _check_close(name, got, want),
                     "compile_s": first - steady, "run_s": steady}
        say(f"kernel {name}: compile {first - steady!r} s, "
            f"run {steady!r} s (smoke figures)")
    return out


def moe_phase(seed: int) -> dict:
    """The dropless MoE layer at granite-moe-3b-a800m widths (d_model
    1536, 40 experts of 512, top-8) over one 2048-token prompt: the
    grouped-matmul kernel path against per-token dense gating computed in
    float32 on the same device, under the kernel tolerance.  The layer
    routes on router logits at the default matmul precision (one bf16 pass
    on a TPU), the reference at float32: tokens whose top-8 sets differ
    between the two (near-ties at the 8th expert) are left out and
    counted.  Prints the share of the grouped matmul's rows that pad
    groups to whole tiles."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import get_config
    from repro.models import moe

    cfg = get_config(MOE_ARCH)
    k = cfg.moe.top_k
    p = moe.init_moe(jax.random.key(seed), cfg)
    x = jax.random.normal(jax.random.key(seed + 1), (1, MOE_TOKENS,
                                                     cfg.d_model),
                          jnp.bfloat16)
    if not moe._kernel_ok():
        raise SmokeFailure("the MoE layer would not take the Pallas kernel")
    layer = jax.jit(lambda x, p: moe.apply_moe(x, p, cfg)[0])
    got, first, steady = _timed(layer, x, p)
    experts = jax.jit(lambda x, r: jnp.sort(jax.lax.top_k(
        moe.router_probs(x, r), k)[1], axis=-1))
    routed = experts(x, p["router"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, p: moe.apply_moe_dense(
            x.astype(jnp.float32), p, cfg, compute=jnp.float32)[0])(x, p)
        same = np.asarray(jnp.all(routed == experts(x, p["router"]),
                                  axis=-1))[0]
    say(f"kernel moe_dropless: {int((~same).sum())} of {MOE_TOKENS} tokens "
        f"route differently at float32 (near-ties), left out")
    err = _check_close("moe_dropless", np.asarray(got, np.float32)[0][same],
                       np.asarray(want)[0][same])
    rows, tile = moe.dropless_rows(MOE_TOKENS, cfg)
    pairs = MOE_TOKENS * k
    say(f"kernel moe_dropless: compile {first - steady!r} s, run "
        f"{steady!r} s (smoke figures); moe_rows_run / moe_rows = "
        f"{rows}/{pairs} = {rows / pairs!r} (tile {tile})")
    return {"max_abs_err": err, "rows_ratio": rows / pairs}


def init_phase(cfg, seed: int) -> dict:
    """The staged weights init a serve image dispatches on each bind
    (``jax.jit(bundle.init)``) at ``cfg``'s widths, against the eager
    ``bundle.init`` on the device: every layer leaf bitwise, the rest
    within one float32 ulp (XLA folds the embedding's scale).  Then the
    same init for granite-moe-3b-a800m at the benchmark's 16 layers is
    compiled for this chip, and its temp bytes are held to
    ``INIT_TEMP_LIMIT``."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models.api import build_model

    bundle = build_model(cfg)
    key = jax.random.key(seed)
    staged, first, steady = _timed(jax.jit(bundle.init), key)
    eager, eager_first, eager_steady = _timed(bundle.init, key)
    for name in staged:
        same = [bool(jnp.array_equal(g, w)) if name == "layers" else bool(
                    jnp.all(jnp.abs(g - w) <= jnp.nextafter(
                        jnp.abs(w), jnp.inf) - jnp.abs(w)))
                for g, w in zip(jax.tree.leaves(staged[name]),
                                jax.tree.leaves(eager[name]))]
        say(f"init {name}: {sum(same)} of {len(same)} leaves "
            f"{'bitwise' if name == 'layers' else 'within 1 ulp'} of the "
            f"eager init")
        if not all(same):
            raise SmokeFailure(f"the staged init's {name} departs from the "
                               f"eager init")
    del staged, eager
    say(f"init {cfg.name}: staged compile {first - steady!r} s, run "
        f"{steady!r} s; eager first {eager_first!r} s, then "
        f"{eager_steady!r} s (smoke figures, each to block_until_ready)")
    granite = dataclasses.replace(get_config(TP_ARCH), num_layers=TP_LAYERS)
    temp = jax.jit(build_model(granite).init).lower(
        key).compile().memory_analysis().temp_size_in_bytes
    say(f"init {TP_ARCH} ({TP_LAYERS} layers) compiled: temp {temp} bytes "
        f"(limit {INIT_TEMP_LIMIT:.3g})")
    if temp > INIT_TEMP_LIMIT:
        raise SmokeFailure(f"the staged init holds {temp} bytes of temp")
    return {"temp_bytes": temp}


def decode_step_has_kernel(cfg) -> bool:
    """Lower the engine's decode step for ``cfg`` on abstract shapes and
    look for the Pallas custom call in its HLO."""
    import jax
    import jax.numpy as jnp
    from repro.models.api import build_model, init_decode_state
    from repro.serving.engine import make_engine_step

    bundle = build_model(cfg)
    params = jax.eval_shape(bundle.init, jax.random.key(0))
    state = jax.eval_shape(lambda: init_decode_state(
        cfg, SLOTS, MAX_LEN, kv="paged"))
    active = jax.ShapeDtypeStruct((SLOTS,), bool)
    budget = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    text = make_engine_step(bundle, MAX_LEN).lower(
        params, state, active, budget).as_text()
    return "tpu_custom_call" in text


def decode_step_pool_copies(cfg, slots: int, max_len: int, *,
                            sharding=None) -> list[str]:
    """Compile the engine's decode step for ``cfg`` at ``slots x max_len``
    (paged KV, default block size) on abstract shapes and return the
    copies in its HLO shaped like a KV pool
    (:func:`repro.launch.hlo_stats.pool_copies`).  Prints the layout the
    compiled step takes each pool in.  ``sharding`` places the shapes
    (a described chip); None compiles for the default device."""
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_stats import pool_copies
    from repro.models.api import (build_model, default_num_blocks,
                                  init_decode_state)
    from repro.serving.engine import make_engine_step

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    bundle = build_model(cfg)
    params = jax.tree.map(spec, jax.eval_shape(bundle.init,
                                               jax.random.key(0)))
    state = jax.tree.map(spec, jax.eval_shape(lambda: init_decode_state(
        cfg, slots, max_len, kv="paged")))
    active = spec(jax.ShapeDtypeStruct((slots,), bool))
    budget = spec(jax.ShapeDtypeStruct((slots,), jnp.int32))
    compiled = make_engine_step(bundle, max_len).lower(
        params, state, active, budget).compile()
    formats = compiled.input_formats[0][1]["cache"][0]
    for key, fmt in sorted(formats.items()):
        say(f"decode step {slots}x{max_len}: pool {key} "
            f"{state['cache'][0][key].shape} layout {fmt.layout}")
    return pool_copies(compiled.as_text(),
                       default_num_blocks(slots, max_len, 16))


# --------------------------------------------------------------------------
# phase 2: serve through the pilot system
# --------------------------------------------------------------------------

def make_trace(vocab: int, n: int, prompt_len, budget, seed: int) -> list:
    """``n`` requests, all due at tick 0: every admission (and so every
    prefill compile) lands in the first tick, before the pilot's
    straggler monitor starts comparing step times."""
    import numpy as np
    rng = np.random.default_rng(seed)
    trace = []
    for rid in range(n):
        plen = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        trace.append({
            "rid": rid,
            "prompt": rng.integers(0, vocab, size=plen).tolist(),
            "max_new_tokens": int(rng.integers(budget[0], budget[1] + 1)),
            "at_step": 0,
        })
    return trace


def serve_through_pilot(images: list, traces: list, *,
                        max_wall: float = 900.0) -> list[dict]:
    """Run one serve task per (image, trace) on ONE pilot, in order.
    Returns, per task, ``{"exitcode", "error", "telemetry"}``."""
    from repro.core.cluster import ClusterSim
    from repro.core.pilot import PilotConfig

    sim = ClusterSim()
    tids = [sim.repo.submit(img, n_steps=10_000, max_wall=max_wall,
                            max_attempts=1, payload_spec={"trace": tr})
            for img, tr in zip(images, traces)]
    (slice_,) = sim.provision(1)
    pilot = sim.spawn_pilot(slice_, PilotConfig(max_payloads=len(images),
                                                idle_grace=1.0))
    drained = sim.run_until_drained(timeout=max_wall * len(images))
    sim.join_all(timeout=60.0)
    if not drained:
        raise SmokeFailure(f"repo did not drain: {sim.repo.stats()}")
    by_task = {rec["task_id"]: rec for rec in pilot.history}
    out = []
    for tid in tids:
        rec = by_task.get(tid, {})
        res = sim.repo.result(tid)
        out.append({
            "exitcode": rec.get("exitcode"),
            "error": rec.get("error") or rec.get("payload_error"),
            "bind_seconds": rec.get("bind_seconds"),
            "telemetry": res.telemetry if res is not None else {},
        })
    return out


def check_served(name: str, run: dict, trace: list, vocab: int) -> dict:
    """The pilot-phase contract for one payload's outcome."""
    tel = run["telemetry"]
    if run["exitcode"] != 0 or run["error"] or tel.get("error"):
        raise SmokeFailure(f"{name}: payload exit code {run['exitcode']}: "
                           f"{run['error'] or tel.get('error')}")
    sv = tel.get("serve", {})
    tokens = tel.get("tokens", {})
    say(f"{name}: payload exit code 0, completed {sv.get('completed')}/"
        f"{len(trace)}, decode steps {sv.get('decode_steps')}, "
        f"d2h transfers {sv.get('d2h_transfers')}")
    for req in trace:
        got = tokens.get(str(req["rid"]))
        # the admission token plus one token per unit of budget
        want = req["max_new_tokens"] + 1
        if got is None or len(got) != want:
            raise SmokeFailure(
                f"{name}: request {req['rid']} got "
                f"{None if got is None else len(got)} tokens, want {want}")
        bad = [t for t in got if not 0 <= t < vocab]
        if bad:
            raise SmokeFailure(f"{name}: request {req['rid']} emitted "
                               f"out-of-vocab tokens {bad[:4]}")
    if sv.get("completed") != len(trace):
        raise SmokeFailure(f"{name}: completed {sv.get('completed')} of "
                           f"{len(trace)}")
    if sv.get("d2h_transfers") != sv.get("decode_steps"):
        raise SmokeFailure(f"{name}: {sv.get('d2h_transfers')} transfers "
                           f"for {sv.get('decode_steps')} decode steps")
    ticks = sorted(tel.get("step_times", []))     # the wrapper keeps 16
    say(f"{name}: all {len(trace)} requests at full budget, tokens in "
        f"vocab; ttft p50 {sv.get('ttft_p50_s')!r} s, tok/s "
        f"{sv.get('tok_per_s')!r}, bind {run['bind_seconds']!r} s, payload "
        f"wall {tel.get('wall')!r} s, median of the last {len(ticks)} "
        f"ticks {ticks[len(ticks) // 2] if ticks else None!r} s (smoke "
        f"figures, first tick compiles)")
    return sv


def serve_image(arch: str, max_len: int, slots: int, extra_flags=(),
                mesh_shape=None):
    from repro.core.images import PayloadImage
    return PayloadImage(arch, f"custom:{max_len}x{slots}", "serve",
                        smoke=False,
                        flags=(("attn_impl", "pallas"),) + tuple(extra_flags),
                        mesh_shape=mesh_shape)


def one_chip(seed: int, meter: CompileMeter) -> None:
    img = serve_image(SERVE_ARCH, MAX_LEN, SLOTS)
    cfg = img.config()
    t0 = time.monotonic()
    kernel_phase(cfg, seed)
    moe_phase(seed)
    init_phase(cfg, seed)
    say(f"kernel phase: {time.monotonic() - t0!r} s")
    meter.lap("kernel phase")
    if not decode_step_has_kernel(cfg):
        raise SmokeFailure("the decode step's HLO has no tpu_custom_call")
    say("decode step HLO contains tpu_custom_call")
    copies = decode_step_pool_copies(cfg, POOL_SLOTS, POOL_MAX_LEN)
    say(f"decode step {POOL_SLOTS}x{POOL_MAX_LEN}: {len(copies)} pool-sized "
        f"copies {copies}")
    if copies:
        raise SmokeFailure("the decode step re-lays out or copies KV pools")
    trace = make_trace(cfg.vocab_size, N_REQUESTS, PROMPT_LEN, BUDGET, seed)
    t0 = time.monotonic()
    (run,) = serve_through_pilot([img], [trace])
    check_served(f"pilot {SERVE_ARCH}", run, trace, cfg.vocab_size)
    say(f"pilot phase: {time.monotonic() - t0!r} s")
    meter.lap("pilot phase")


def four_chips(seed: int, meter: CompileMeter) -> None:
    depth = (("num_layers", TP_LAYERS),)
    single = serve_image(TP_ARCH, TP_MAX_LEN, TP_SLOTS, depth)
    sharded = serve_image(TP_ARCH, TP_MAX_LEN, TP_SLOTS, depth,
                          mesh_shape=TP_MESH)
    cfg = single.config()
    trace = make_trace(cfg.vocab_size, TP_REQUESTS, TP_PROMPT_LEN,
                       TP_BUDGET, seed)
    t0 = time.monotonic()
    runs = serve_through_pilot([single, sharded], [trace, trace])
    say(f"four-chip phase: {time.monotonic() - t0!r} s")
    meter.lap("four-chip phase")
    sv1 = check_served(f"{TP_ARCH} 1 device", runs[0], trace,
                       cfg.vocab_size)
    svm = check_served(f"{TP_ARCH} mesh {TP_MESH}", runs[1], trace,
                       cfg.vocab_size)
    say(f"KV bytes per device: 1 device {sv1['kv_pool_bytes_per_device']}, "
        f"mesh {svm['kv_pool_bytes_per_device']} (total "
        f"{svm['kv_pool_bytes']}, mesh devices {svm['mesh_devices']})")
    t1, tm = runs[0]["telemetry"]["tokens"], runs[1]["telemetry"]["tokens"]
    same = 0
    for req in trace:
        a, b = t1[str(req["rid"])], tm[str(req["rid"])]
        first = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
        if first is None:
            same += 1
            say(f"rid {req['rid']}: {len(a)} greedy tokens identical")
        else:
            say(f"rid {req['rid']}: tokens diverge at index {first} of "
                f"{len(a)} (1 device {a[first:first + 4]}, mesh "
                f"{b[first:first + 4]})")
    say(f"token parity: {same}/{len(trace)} requests identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devices = require_tpu(args.chips)
        import_repo()
        from repro.launch.compile_cache import enable_compile_cache
        say(f"compile cache at {enable_compile_cache()}")
        say(f"device {devices[0].device_kind}, {len(devices)} chip(s)")
        meter = CompileMeter()
        if args.chips == 4:
            four_chips(args.seed, meter)
        else:
            one_chip(args.seed, meter)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
