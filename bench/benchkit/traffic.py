"""The one traffic generator: a mix file's parameters -> requests.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the mix's distributions); the
seed only permutes their order and draws the token ids.  So two seeds do
the same work in another order, and a run's spread is the system's, not
the dice's.

Length distributions (``prompt_len`` / ``output_len``)::

    {"dist": "fixed", "value": 256}
    {"dist": "uniform", "min": 256, "max": 768}
    {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32, "max": 1536}

Lengths are used as drawn: nothing snaps them to the engine's buckets.
``output_len`` counts the tokens the client receives; the serve engine
emits one token at admission and one per unit of ``max_new_tokens``.
"""

from __future__ import annotations

import math
import statistics

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of ``dist`` in ascending order."""
    kind = dist["dist"]
    q = _quantiles(n)
    if kind == "fixed":
        out = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        out = lo + q * (hi - lo)
    elif kind == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        out = np.exp([nd.inv_cdf(float(x)) for x in q])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", math.inf)
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps (seconds) of a Poisson process."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return -np.log1p(-_quantiles(n)) / float(arrivals["rate_per_s"])


def requests(mix: dict, seed: int, n: int, vocab: int, *,
             rid0: int = 0) -> list[dict]:
    """``n`` requests in the engine's trace-entry format, plus
    ``output_len`` (tokens the client should receive)."""
    rng = np.random.default_rng(seed)
    plens = rng.permutation(lengths(mix["prompt_len"], n))
    olens = rng.permutation(lengths(mix["output_len"], n))
    out = []
    for i in range(n):
        out.append({
            "rid": rid0 + i,
            "prompt": rng.integers(0, vocab, size=int(plens[i])).tolist(),
            "max_new_tokens": int(olens[i]) - 1,
            "output_len": int(olens[i]),
        })
    return out


def schedule(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Due offsets (s) of an open loop's arrivals inside ``horizon_s``:
    enough stratified gaps to cover the horizon, in seeded order."""
    rate = float(mix["arrivals"]["rate_per_s"])
    n = int(math.ceil(rate * horizon_s)) + 1
    g = np.random.default_rng(seed ^ 0x5EED).permutation(
        gaps(mix["arrivals"], n))
    due = np.cumsum(g) - g[0]            # the first request is due at 0
    return due[due < horizon_s]
