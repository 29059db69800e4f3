"""Decide ``correct``: served tokens against the plain reference.

For each sampled request the reference runs once over the prompt as sent
followed by the served tokens, and at every served position reads how far
the served token's logit lies below the reference's best there.  The
widest such gap over the sample is the number compared; greedy decoding
that agrees with the reference reads 0, a token chosen on bf16 rounding
between near-ties reads the size of the tie.

The control puts the reference in the program's place at the precision
below the configuration's (float8 for bfloat16 compute) and reads the same
gap for the token the control ranks first at each of those positions.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchkit import reference


def sample(requests: list, n: int, seed: int) -> list:
    """``n`` completed requests drawn from the seed, the longest among
    them."""
    done = [r for r in requests if not r["failed"]]
    if len(done) <= n:
        return done
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    idx = np.random.default_rng(seed).choice(len(rest), n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(idx)]


@jax.jit
def _gaps(ref, ctl, targets, mask):
    """Per-row widest gap of ``targets`` (and of the control's choice)
    below the reference's best; masked positions read 0."""
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, targets[..., None], axis=-1)[..., 0]
    pick = jnp.argmax(ctl, axis=-1)
    alt = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
    return (jnp.max(jnp.where(mask, best - got, 0.0), axis=-1),
            jnp.max(jnp.where(mask, best - alt, 0.0), axis=-1))


def gap_readings(model_cfg: dict, seed: int, reqs: list, *,
                 batch_tokens: int, control: bool = False) -> list[dict]:
    """Per request: ``served`` gap, and ``control`` gap when asked."""
    m = reference.Model.from_config(model_cfg)
    out = []
    if not reqs:
        return out
    seqs = [list(r["prompt"]) + list(r["tokens"][:-1]) for r in reqs]
    s_pad = 128 * math.ceil(max(map(len, seqs)) / 128)
    rows = max(1, batch_tokens // s_pad)
    for b0 in range(0, len(reqs), rows):
        chunk = list(range(b0, min(b0 + rows, len(reqs))))
        toks = np.zeros((rows, s_pad), np.int32)
        tgt = np.zeros((rows, s_pad), np.int32)
        mask = np.zeros((rows, s_pad), bool)
        for j, i in enumerate(chunk):
            p, t = len(reqs[i]["prompt"]), reqs[i]["tokens"]
            toks[j, :len(seqs[i])] = seqs[i]
            tgt[j, p - 1:p - 1 + len(t)] = t
            mask[j, p - 1:p - 1 + len(t)] = True
        ref = reference.forward(m, seed, toks)
        ctl = reference.forward(m, seed, toks, low="fp8") if control \
            else ref
        served, alt = jax.device_get(_gaps(ref, ctl, jnp.asarray(tgt),
                                           jnp.asarray(mask)))
        del ref, ctl
        for j, i in enumerate(chunk):
            rec = {"rid": reqs[i]["rid"], "served": float(served[j])}
            if control:
                rec["control"] = float(alt[j])
            out.append(rec)
    return out


def malformed(reqs: list, vocab: int) -> int:
    """Completed requests whose answer has the wrong length or a token
    outside the vocabulary."""
    bad = 0
    for r in reqs:
        t = r["tokens"]
        if len(t) != r["output_len"] or any(not 0 <= x < vocab for x in t):
            bad += 1
    return bad


def judge(cfg: dict, check: dict, run, seed: int, *,
          control: bool = False) -> tuple[bool, dict, list]:
    """(correct, {name: [value, limit]}, per-request readings)."""
    vocab = int(cfg["model"]["vocab_size"])
    done = [r for r in run.requests if not r["failed"]]
    picked = sample(run.requests, int(check["sample_requests"]), seed)
    readings = gap_readings(cfg["model"], seed, picked,
                            batch_tokens=int(check["batch_tokens"]),
                            control=control)
    gap = max((x["served"] for x in readings), default=math.inf)
    numbers = {
        "served_gap": [gap, float(check["served_gap_limit"])],
        "missing_answers": [len(run.requests) - len(done), 0],
        "malformed_answers": [malformed(done, vocab), 0],
        "compared_tokens": [sum(len(r["tokens"]) for r in picked),
                            int(check["min_compared_tokens"])],
    }
    return passes(numbers), numbers, readings


def passes(numbers: dict) -> bool:
    """Every compared number within its limit (``compared_tokens`` is a
    floor, the others ceilings)."""
    return (numbers["served_gap"][0] <= numbers["served_gap"][1]
            and numbers["missing_answers"][0] == 0
            and numbers["malformed_answers"][0] == 0
            and numbers["compared_tokens"][0]
            >= numbers["compared_tokens"][1])
