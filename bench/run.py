#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name:
``BENCHMARK.json`` names the cell's configuration file and traffic mix
(``bench/traffic/<mix>.json``), ``bench/cells/<cell>.json`` holds the
cell's comparison limits, and each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or
a metric adds files and entries; nothing here changes.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  ``correct`` comes from comparing the served tokens with the
plain float32 reference (``benchkit/compare.py``).  The last lines on
standard error, and the ``checks`` key that ends the result line, give
each compared number beside its limit.  Without a TPU, with fewer chips
than the cell asks for, or without the program's ``src/`` beside this
directory, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


class Refused(Exception):
    """The run cannot be made here; no result is printed."""


def load_cell(root: pathlib.Path, name: str) -> dict:
    """Everything the cell names, read from the files that hold it."""
    bench_dir = root / "bench"
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    return {
        "cell": cell,
        "cfg": json.loads((root / cfg_entry["file"]).read_text()),
        "mix": json.loads((bench_dir / "traffic"
                           / f"{cell['traffic']}.json").read_text()),
        "check": json.loads((bench_dir / "cells"
                             / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
        "peaks": json.loads((bench_dir / "peaks.json").read_text()),
    }


def require_chips(n: int):
    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise Refused(f"needs a TPU; JAX found platform {platform!r}")
    if jax.device_count() < n:
        raise Refused(f"needs {n} chips; JAX found {jax.device_count()}")


def import_program(root: pathlib.Path) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"the program's sources are not at {src}")
    sys.path.insert(0, str(src))


def enable_cache(root: pathlib.Path) -> None:
    """Persistent compile cache at the checkout's fixed path, whatever
    ``JAX_COMPILATION_CACHE_DIR`` says, so two checkouts share nothing;
    every compile is kept, so only a cell's first run in a checkout
    compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_metrics(specs: list, ctx) -> dict:
    from benchkit.readers import load_module
    out = {}
    for m in specs:
        value = load_module(
            ctx.bench_dir / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, root: pathlib.Path | None = None,
         chip: bool = True) -> int:
    t_main = time.monotonic()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or BENCH.parent
    # the TPU runtime's logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        spec = load_cell(root, args.workload)
        if chip:
            require_chips(int(spec["cell"]["chips"]))
        import_program(root)
        enable_cache(root)
        import jax
        from benchkit import compare, loads, trace
        from benchkit.readers import Ctx
        dev = jax.devices()[0]
        peaks = spec["peaks"]["devices"].get(dev.device_kind)
        if peaks is None:
            if chip:
                raise Refused(f"device kind {dev.device_kind!r} is not in "
                              f"bench/peaks.json")
            peaks = {}
        trace_dir = None
        if args.trace:
            trace_dir = str(root / ".bench_trace" / args.workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
        meter = loads.CompileMeter()
        t_drive = time.monotonic()
        run = loads.LOADS[spec["mix"]["kind"]](
            spec["cfg"], spec["mix"], args.seed, args.seconds, meter,
            trace_dir)
        run.setup_s += t_drive - t_main
        rows = None
        if trace_dir:
            rows = trace.load(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        gc.collect()
        correct, numbers, _ = compare.judge(spec["cfg"], spec["check"], run,
                                            args.seed)
        ctx = Ctx(run=run, model=spec["cfg"]["model"], mix=spec["mix"],
                  peaks=peaks, bench_dir=root / "bench", rows=rows)
        metrics = read_metrics(
            spec["per_layer" if args.trace else "end_to_end"], ctx)
    except Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": len(run.requests),
              "failed": sum(1 for r in run.requests if r["failed"]),
              "metrics": metrics, "device": device}
    if rows is not None:
        device["busy_s"] = trace.busy_s(rows)
        device["window_s"] = run.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(rows),
                               "idle_gaps": trace.idle_gaps(rows)}
    print(f"[bench] {args.workload} seed {args.seed}: set-up "
          f"{run.setup_s!r} s ({run.compiles_setup} compiles), window "
          f"{run.window_s!r} s ({run.compiles_window} compiles), generator "
          f"late by at most {run.lag_max_s!r} s", file=sys.stderr)
    for name, (value, limit) in numbers.items():
        rule = ">=" if name == "compared_tokens" else "<="  # lower limit
        print(f"[bench] check {name}: {value!r} {rule} {limit!r}",
              file=sys.stderr)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
