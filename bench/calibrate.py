#!/usr/bin/env python3
"""Readings that set a cell's comparison limit: the program's and the
control's, on many seeds, in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 6

For each seed it drives the cell as ``run.py`` does (same set-up, a
window of ``--seconds`` at the cell's own load), then reads, over the
same sampled requests, the served tokens' widest gap below the float32
reference (the program's reading) and the widest gap of the token the
fp8 control ranks first (the control's reading).  One JSON line per
seed.  The benchmark's own runs never compute the control.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import run as bench_run
    root = BENCH.parent
    spec = bench_run.load_cell(root, args.workload)
    bench_run.require_chips(int(spec["cell"]["chips"]))
    bench_run.import_program(root)
    bench_run.enable_cache(root)
    from benchkit import compare, loads
    meter = loads.CompileMeter()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = loads.LOADS[spec["mix"]["kind"]](
            spec["cfg"], spec["mix"], seed, args.seconds, meter, None)
        gc.collect()
        correct, numbers, readings = compare.judge(
            spec["cfg"], spec["check"], run, seed, control=True)
        plen = {r["rid"]: len(r["prompt"]) for r in run.requests}
        control_gap = max((r["control"] for r in readings), default=math.inf)
        # the control in the program's place: the same answers, judged by
        # the gap of the tokens it ranks first
        control_correct = compare.passes(
            {**numbers, "served_gap": [control_gap,
                                       numbers["served_gap"][1]]})
        print(json.dumps({
            "seed": seed, "correct": correct,
            "control_correct": control_correct,
            "served_gap": numbers["served_gap"][0],
            "control_gap": control_gap,
            "compared_tokens": numbers["compared_tokens"][0],
            "missing": numbers["missing_answers"][0],
            "malformed": numbers["malformed_answers"][0],
            "setup_s": run.setup_s, "window_s": run.window_s,
            "requests": len(run.requests),
            "compiles": [run.compiles_setup, run.compiles_window],
            "memory_peak_bytes": run.memory_peak_bytes,
            # per compared request: prompt length, served and control gap
            "readings": [[plen[r["rid"]], r["served"], r["control"]]
                         for r in readings],
            # per bind (rebind mixes): serve shape, submit -> first tick (s)
            "binds": [[b["image"], b["bind_ttft_s"]] for b in run.binds],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
