"""What a metric reader gets, and the arithmetic readers share.

A reader is ``bench/metrics/<metric name>.py`` defining ``read(ctx)``,
which returns the metric's value or None when the run holds nothing to
read (the metric is then left out of the result line).  Readers of a
share of a roofline or a peak return None, never 0, when the trace holds
no event of theirs.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
import pathlib

import numpy as np

from benchkit import trace

def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Ctx:
    run: object                 # loads.Run
    model: dict                 # the configuration's ``model`` sizes
    mix: dict
    peaks: dict                 # the device kind's row of peaks.json
    bench_dir: pathlib.Path     # the checkout's ``bench/``
    rows: list | None = None    # trace events (``--trace 1`` only)

    def cost(self, name: str):
        """``bench/costs/<name>.py``."""
        return load_module(self.bench_dir / "costs" / f"{name}.py")

    def busy_s(self) -> float | None:
        if self.rows is None:
            return None
        b = trace.busy_s(self.rows)
        return b if b > 0 else None


def percentile(values, q: float) -> float | None:
    v = [x for x in values if x is not None]
    return float(np.percentile(v, q)) if v else None


def mean(values) -> float | None:
    v = [x for x in values if x is not None]
    return float(sum(v) / len(v)) if v else None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> float | None:
    """Least time the chip could take over the time taken, in %."""
    if seconds <= 0 or not math.isfinite(seconds) or not peaks:
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
