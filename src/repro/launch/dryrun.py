import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax ---------------------------------------
"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract the roofline terms from the compiled artifact.

  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]

Single-cell mode runs in-process; ``--all`` spawns one subprocess per cell
(fresh XLA state, bounded memory, ``JAX_PLATFORMS=cpu`` so a child never
contends for an accelerator) and aggregates JSON records under
``results/dryrun/<mesh>/``.  The 512 placeholder host devices exist ONLY in
this entrypoint — nothing else in the repo sets XLA_FLAGS.
"""

import argparse
import dataclasses
import json
import pathlib
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, applicable_shapes, get_config, list_archs
from repro.launch import hw
from repro.launch.hlo_cost import module_cost
from repro.launch.hlo_stats import collective_stats, cost_summary, memory_summary
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.optim.adamw import OptimConfig
from repro.runtime import sharding as shd

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _shardings_for(cfg, shape, mode, mesh, specs, moe_partition="tp",
                   layout="2d"):
    """(in_shardings, out_shardings, donate_argnums) for the step kind."""
    if mode == "train":
        state_sh = shd.train_state_shardings(specs[0]["params"], mesh,
                                             moe_partition=moe_partition,
                                             layout=layout)
        batch_sh = shd.batch_shardings(specs[1], mesh, layout)
        metrics_sh = NamedSharding(mesh, P())
        return (state_sh, batch_sh), (state_sh, metrics_sh), (0,)
    if mode == "prefill":
        param_sh = shd.param_shardings(specs[0], mesh, "serve",
                                       moe_partition=moe_partition,
                                       layout=layout)
        batch_sh = shd.batch_shardings(specs[1], mesh, layout)
        return (param_sh, batch_sh), None, ()
    # decode
    param_sh = shd.param_shardings(specs[0], mesh, "serve",
                                   moe_partition=moe_partition, layout=layout)
    state_sh = shd.decode_state_shardings(specs[1], mesh)
    return (param_sh, state_sh), (None, state_sh), (1,)


def _step_fn(cfg, mode, flags: dict):
    if mode == "train":
        return make_train_step(cfg, OptimConfig(total_steps=10_000))
    if mode == "prefill":
        return make_prefill_step(cfg)
    return make_serve_step(cfg)


def _model_flops(cfg, shape, mode) -> float:
    n = cfg.active_param_count()
    if mode == "train":
        return 6.0 * n * shape.tokens
    if mode == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch          # decode: 1 new token/seq


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             flags: dict | None = None, save_hlo: bool = False,
             moe_partition: str = "tp", layout: str = "2d") -> dict:
    flags = flags or {}
    cfg = get_config(arch)
    if flags:
        cfg = dataclasses.replace(cfg, **flags)
    shape = SHAPES[shape_name]
    mode = shape.mode
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names)},
        "flags": flags, "moe_partition": moe_partition, "layout": layout,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }

    specs = input_specs(cfg, shape, mode)
    in_sh, out_sh, donate = _shardings_for(cfg, shape, mode, mesh, specs,
                                           moe_partition, layout)
    step = _step_fn(cfg, mode, flags)

    t0 = time.monotonic()
    with mesh, shd.activation_sharding(mesh, layout):
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*specs)
        rec["lower_seconds"] = time.monotonic() - t0
        t1 = time.monotonic()
        compiled = lowered.compile()
        rec["compile_seconds"] = time.monotonic() - t1

    rec["memory"] = memory_summary(compiled)
    # XLA cost_analysis counts while-loop (scan) bodies ONCE — kept only as
    # a reference.  The roofline reads from the trip-count-aware HLO walk.
    rec["cost_analysis_raw"] = cost_summary(compiled)
    hlo = compiled.as_text()
    rec["collectives_raw"] = collective_stats(hlo)
    mc = module_cost(hlo)
    rec["hlo_cost"] = {
        "flops": mc.flops,
        "bytes_unfused": mc.bytes,
        "bytes_fused": mc.bytes_fused,
        "transcendentals": mc.transcendentals,
        "collective_bytes": mc.collective_bytes,
        "collective_counts": mc.collective_counts,
        "total_collective_bytes": mc.total_collective_bytes,
        "top_collectives": [
            {"op": k[0], "type": k[1], "trips": k[2], "bytes": v}
            for k, v in mc.top_collectives()],
    }
    if save_hlo:
        rec["hlo_path"] = str(RESULTS / "hlo" / f"{arch}__{shape_name}.txt")
        p = pathlib.Path(rec["hlo_path"])
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(hlo)

    # ---- roofline terms (seconds, per device == per the assignment's
    # global-bytes / (chips x bw) convention) -------------------------------
    flops_dev = mc.flops
    bytes_dev = mc.bytes_fused        # TPU-fusion convention (see hlo_cost)
    coll_dev = mc.total_collective_bytes
    terms = {
        "compute_s": flops_dev / hw.PEAK_FLOPS,
        "memory_s": bytes_dev / hw.HBM_BW,
        "collective_s": coll_dev / hw.ICI_BW,
    }
    terms["dominant"] = max(terms, key=lambda k: terms[k] if k.endswith("_s") else -1)
    model_flops = _model_flops(cfg, shape, mode)
    terms["model_flops_global"] = model_flops
    terms["model_flops_per_chip"] = model_flops / n_chips
    terms["useful_flops_ratio"] = (
        model_flops / n_chips / flops_dev if flops_dev else None)
    bound_s = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["roofline_step_s"] = bound_s
    terms["roofline_fraction"] = (
        (model_flops / n_chips / hw.PEAK_FLOPS) / bound_s if bound_s else None)
    rec["roofline"] = terms

    # fits-in-HBM check
    mem = rec["memory"].get("total_nonalias_bytes")
    rec["fits_hbm"] = None if mem is None else bool(mem < hw.HBM_BYTES)
    return rec


# --------------------------------------------------------------------------
# serve-mesh accounting: per-SHARD memory / FLOPs for a mesh-bound serve
# image, without building the mesh (pure shape math + the sharding rules'
# divisor mirrors) — what the dry run previously got wrong by quoting
# whole-pool numbers for a sharded engine.
# --------------------------------------------------------------------------


def run_serve_cell(arch: str, *, mesh_shape: tuple = (1, 1),
                   slots: int = 4, max_len: int | None = None,
                   kv: str = "paged", num_blocks: int | None = None,
                   block_size: int = 16, smoke: bool = False) -> dict:
    """Roofline accounting for ONE serve engine on a ``(data, model)``
    mesh.  Everything is ``jax.eval_shape`` + the pure shard-factor
    mirrors of the serve sharding rules (`serve_param_shard_factor` /
    `serve_state_shard_factor`), so this runs in milliseconds on any
    host: per-device bytes divide each leaf by exactly the factor the
    real `serve_*_shardings` would apply (divisibility-gated, dtype
    aware), instead of pretending the whole pool lives on every chip."""
    from repro.configs.base import get_smoke_config
    from repro.models.api import build_model, init_decode_state

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    msz = int(mesh_shape[1])
    n_dev = int(mesh_shape[0]) * msz
    ml = max_len or 1024
    bundle = build_model(cfg)
    params = jax.eval_shape(bundle.init, jax.random.key(0))
    state = jax.eval_shape(
        lambda: init_decode_state(cfg, slots, ml, kv=kv,
                                  num_blocks=num_blocks,
                                  block_size=block_size))

    def _account(tree, factor_fn):
        total = [0]
        per_dev = [0]
        def one(path, leaf):
            b = int(leaf.size) * leaf.dtype.itemsize
            total[0] += b
            per_dev[0] += b // factor_fn(path, leaf.shape, msz)
        jax.tree_util.tree_map_with_path(one, tree)
        return total[0], per_dev[0]

    p_total, p_dev = _account(params, shd.serve_param_shard_factor)
    s_total, s_dev = _account(state, shd.serve_state_shard_factor)
    kv_leaves = {"kp", "vp", "ckvp", "kropep", "k", "v", "ckv", "krope"}
    kv_total = [0]
    kv_dev = [0]
    def kv_one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if str(name) not in kv_leaves:
            return
        b = int(leaf.size) * leaf.dtype.itemsize
        kv_total[0] += b
        kv_dev[0] += b // shd.serve_state_shard_factor(path, leaf.shape, msz)
    jax.tree_util.tree_map_with_path(kv_one, state)

    # decode FLOPs: one token per slot per step.  The column-parallel
    # shards split the matmul work over the model axis; the data axis
    # replicates the engine's batch (one engine spans the whole mesh), so
    # per-device work divides by the MODEL size only.
    flops_global = 2.0 * cfg.active_param_count() * slots
    flops_dev = flops_global / msz
    mem_dev = p_dev + s_dev
    return {
        "arch": arch, "mode": "serve", "mesh_shape": list(mesh_shape),
        "mesh_devices": n_dev, "slots": slots, "max_len": ml, "kv": kv,
        "params_bytes": p_total, "params_bytes_per_device": p_dev,
        "state_bytes": s_total, "state_bytes_per_device": s_dev,
        "kv_pool_bytes": kv_total[0],
        "kv_pool_bytes_per_device": kv_dev[0],
        "bytes_per_device": mem_dev,
        "decode_flops": flops_global,
        "decode_flops_per_device": flops_dev,
        "decode_compute_s": flops_dev / hw.PEAK_FLOPS,
        "decode_memory_s": mem_dev / hw.HBM_BW,
        "fits_hbm_per_device": bool(mem_dev < hw.HBM_BYTES),
    }


# --------------------------------------------------------------------------


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        for s in applicable_shapes(cfg):
            cells.append((arch, s))
    return cells


def _cell_path(arch, shape_name, multi_pod) -> pathlib.Path:
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    return RESULTS / mesh_tag / f"{arch}__{shape_name}.json"


def run_all(multi_pod: bool, skip_existing: bool, timeout: float = 3000.0):
    cells = all_cells()
    print(f"[dryrun] {len(cells)} cells, multi_pod={multi_pod}")
    failures = []
    for i, (arch, shape_name) in enumerate(cells):
        out = _cell_path(arch, shape_name, multi_pod)
        if skip_existing and out.exists():
            print(f"[{i+1:2d}/{len(cells)}] {arch} x {shape_name}: cached")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape_name]
        if multi_pod:
            cmd.append("--multi-pod")
        t0 = time.monotonic()
        try:
            # the child compiles on placeholder host devices: pinned to
            # the CPU, it never contends for an accelerator
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=timeout,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            ok = r.returncode == 0 and out.exists()
        except subprocess.TimeoutExpired:
            r, ok = None, False
        dt = time.monotonic() - t0
        status = "ok" if ok else "FAIL"
        print(f"[{i+1:2d}/{len(cells)}] {arch} x {shape_name}: {status} "
              f"({dt:.0f}s)")
        if not ok:
            failures.append((arch, shape_name))
            if r is not None:
                tail = (r.stderr or r.stdout or "").strip().splitlines()[-12:]
                print("    " + "\n    ".join(tail))
    print(f"[dryrun] done; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--moe-partition", default="tp", choices=("tp", "ep"))
    ap.add_argument("--layout", default="2d", choices=("2d", "fsdp"))
    ap.add_argument("--flags", default="",
                    help='comma list key=value ArchConfig overrides, e.g. '
                         '"remat=dots,attn_impl=causal_blocked"')
    ap.add_argument("--serve-mesh", default=None,
                    help="per-shard serve accounting on a 'DxM' "
                         "(data, model) mesh — pure shape math, no "
                         "compile; e.g. '1x2'")
    ap.add_argument("--slots", type=int, default=4,
                    help="serve-mesh mode: engine slots")
    ap.add_argument("--serve-max-len", type=int, default=None,
                    help="serve-mesh mode: engine KV length")
    ap.add_argument("--smoke", action="store_true",
                    help="serve-mesh mode: smoke-sized config")
    args = ap.parse_args()

    if args.serve_mesh:
        d, m = args.serve_mesh.lower().split("x")
        rec = run_serve_cell(args.arch, mesh_shape=(int(d), int(m)),
                             slots=args.slots, max_len=args.serve_max_len,
                             smoke=args.smoke)
        print(json.dumps(rec, indent=1))
        return

    if args.all:
        fails = run_all(args.multi_pod, args.skip_existing)
        sys.exit(1 if fails else 0)

    flags = {}
    for kv in filter(None, args.flags.split(",")):
        k, v = kv.split("=")
        flags[k] = int(v) if v.lstrip("-").isdigit() else v

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   flags=flags, save_hlo=args.save_hlo,
                   moe_partition=args.moe_partition, layout=args.layout)
    out = _cell_path(args.arch, args.shape, args.multi_pod)
    if flags or args.moe_partition != "tp" or args.layout != "2d":
        tag = ",".join(f"{k}={v}" for k, v in sorted(flags.items()))
        if args.moe_partition != "tp":
            tag += ("," if tag else "") + f"moe={args.moe_partition}"
        if args.layout != "2d":
            tag += ("," if tag else "") + f"layout={args.layout}"
        out = out.with_name(out.stem + f"__{tag}" + ".json")
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    r = rec["roofline"]
    print(json.dumps({
        "cell": f"{args.arch} x {args.shape}",
        "mesh": rec["mesh"]["shape"],
        "compile_s": round(rec["compile_seconds"], 1),
        "compute_s": r["compute_s"], "memory_s": r["memory_s"],
        "collective_s": r["collective_s"], "dominant": r["dominant"],
        "useful_flops_ratio": r["useful_flops_ratio"],
        "roofline_fraction": r["roofline_fraction"],
        "mem_per_dev_GB": (rec["memory"].get("total_nonalias_bytes", 0) or 0) / 2**30,
        "fits_hbm": rec["fits_hbm"],
    }, indent=1))


if __name__ == "__main__":
    main()
