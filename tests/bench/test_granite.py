"""granite-moe-3b-a800m against the plain float32 reference, on the CPU.

At the granite smoke size (``fixture/configs/granite-smoke.json``: the
repo's smoke preset with the published embedding, attention, residual and
logits scalars and RMSNorm eps 1e-6), the program's weights are the
reference's bit for bit, its prefill logits agree with the reference's,
and so do the logits of a ``ServeEngine`` admission followed by paged
decode steps.  Then the grouped-matmul cost against a hand count, and a
traced CPU run of a granite rebind cell added as files and entries only,
which reports the cell's per-layer metrics.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(BENCH))

from benchkit import reference  # noqa: E402

ARCH = "granite-moe-3b-a800m"
CFG = json.loads((FIXTURE / "configs" / "granite-smoke.json").read_text())
SEEDS = (2 ** 31 + 3, 17, 99)

# Logit tolerances.  The program computes in bfloat16 (f32 weights cast
# per step, f32 accumulation), the reference in float32.  Over the seeds
# above the widest prefill gap read 7.4e-4 against logits of magnitude
# 0.10-0.16 (the published logits scaling divides them by 6): bf16
# rounding of the residual stream, about 2^-8 of it.  PREFILL_ATOL leaves
# 5x that.  The paged decode steps (bf16 KV pool) read at most 7.7e-4
# over the first two seeds; DECODE_ATOL leaves 10x.  The program without
# the published scalars departs by more than the logits' own size
# (test_prefill_without_the_scalars_departs).
PREFILL_ATOL = 4e-3
DECODE_ATOL = 8e-3


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "t_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _program(cfg=None):
    from repro.configs.base import get_smoke_config
    from repro.models.api import build_model
    cfg = cfg or get_smoke_config(ARCH)
    return cfg, build_model(cfg)


def _model():
    return reference.Model.from_config(CFG["model"])


def test_reference_weights_equal_the_programs():
    """The reference makes every layer's weights bit for bit as the serve
    image does, experts and router included (the embedding to rounding),
    and reads the program's sizes and scalars from the fixture."""
    import jax
    cfg, bundle = _program()
    m = _model()
    assert (m.d, m.heads, m.kv_heads, m.head_dim, m.d_ff, m.vocab,
            m.experts, m.top_k, m.layers) == (
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.moe.d_ff_expert, cfg.vocab_size, cfg.moe.num_experts,
        cfg.moe.top_k, cfg.num_layers)
    assert (m.eps, m.emb_mult, m.attn_scale, m.res_mult, m.logits_div) == (
        cfg.norm_eps, cfg.embedding_multiplier, cfg.attention_multiplier,
        cfg.residual_multiplier, cfg.logits_scaling)
    seed = SEEDS[0]
    params = bundle.init(jax.random.key(seed))
    k_embed, k_head, keys = reference.root_keys(seed, m.layers)
    # the embedding's normal draw times 0.02 rounds once differently when
    # the reference's jit fuses the two: equal to the last bit or so
    np.testing.assert_allclose(
        reference.outer_weights(m, k_embed, k_head)["embed"],
        params["embed"], rtol=1e-6, atol=1e-8)
    for layer in range(m.layers):
        w = reference.layer_weights(m, keys[layer])
        got = jax.tree.map(lambda a: a[layer], params["layers"][0])
        for k in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(w[k], got["mixer"][k])
        for k in ("router", "up", "down", "gate"):
            np.testing.assert_array_equal(w[k], got["ffn"][k])


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_logits_agree_with_the_reference(seed):
    import jax
    import jax.numpy as jnp
    _, bundle = _program()
    m = _model()
    params = bundle.init(jax.random.key(seed))
    toks = np.random.default_rng(seed).integers(0, m.vocab, (1, 64))
    logits, _ = jax.jit(bundle.prefill)(params, {"tokens": jnp.asarray(toks)})
    ref = reference.forward(m, seed, toks)
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(ref[0, -1]), atol=PREFILL_ATOL)


def test_prefill_without_the_scalars_departs():
    """The same weights with the scalars and eps left at the defaults
    (what the program ran before it carried them) miss the reference by
    far more than the tolerance: the comparison sees the fault."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    plain = dataclasses.replace(
        get_smoke_config(ARCH), norm_eps=1e-5, embedding_multiplier=1.0,
        attention_multiplier=None, residual_multiplier=1.0,
        logits_scaling=1.0)
    _, bundle = _program(plain)
    m = _model()
    seed = SEEDS[0]
    params = bundle.init(jax.random.key(seed))
    toks = np.random.default_rng(seed).integers(0, m.vocab, (1, 64))
    logits, _ = jax.jit(bundle.prefill)(params, {"tokens": jnp.asarray(toks)})
    ref = reference.forward(m, seed, toks)
    gap = np.abs(np.asarray(logits[0, -1]) - np.asarray(ref[0, -1])).max()
    assert gap > 10 * PREFILL_ATOL


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_engine_admission_and_paged_decode_agree_with_the_reference(seed):
    """A ``ServeEngine`` admits a 64-token prompt (one-shot dropless
    prefill, installed into the paged pool), then 10 decode steps run on
    its paged state.  Each step's logits agree with the reference's full
    forward over the prompt and the tokens served before it."""
    import jax
    from repro.serving.engine import Request, ServeEngine
    cfg, bundle = _program()
    m = _model()
    params = bundle.init(jax.random.key(seed))
    eng = ServeEngine(cfg, params, slots=2, max_len=128, bundle=bundle)
    assert eng.kv == "paged" and eng.prefill_mode == "oneshot"
    prompt = np.random.default_rng(seed).integers(0, m.vocab, 64)
    req = Request(rid=0, prompt=prompt.astype(np.int32), max_new_tokens=32)
    eng.submit(req)
    eng._admit()
    # the counters: pairs routed, and the rows the grouped product ran,
    # at most E*(tile-1) padding rows a layer over the pairs
    from repro.models.moe import dropless_rows
    rows, tile = dropless_rows(64, cfg)
    assert eng.moe_rows == cfg.num_layers * 64 * cfg.moe.top_k
    assert eng.moe_rows_run == cfg.num_layers * rows
    assert eng.moe_rows < eng.moe_rows_run <= eng.moe_rows + (
        cfg.num_layers * cfg.moe.num_experts * (tile - 1))
    slot = next(i for i, s in enumerate(eng.slot_meta) if s.rid == 0)
    decode = jax.jit(bundle.decode)
    served, step_logits = list(req.tokens), []
    for _ in range(10):
        logits, eng.state = decode(params, eng.state)
        step_logits.append(np.asarray(logits[slot, 0]))
        served.append(int(eng.state["token"][slot, 0]))
    ref = np.asarray(reference.forward(
        m, seed, np.asarray([list(prompt) + served[:-1]])))[0]
    # the admission token is the reference's best at the last prompt
    # position unless two logits tie within the prefill tolerance
    best = ref[63].max()
    assert ref[63, served[0]] >= best - PREFILL_ATOL
    for i, got in enumerate(step_logits):
        np.testing.assert_allclose(got, ref[64 + i], atol=DECODE_ATOL)


def test_moe_gmm_cost_hand_count():
    cost = _load(BENCH / "costs" / "moe_gmm.py")
    granite = json.loads((BENCH / "configs"
                          / "granite-moe-3b-a800m-16l.json").read_text())[
        "model"]
    flops, nbytes = cost.cost(granite, 10)
    # 10 tokens x 8 experts = 80 pairs; up, gate and down each 1536 x 512
    # multiply-adds per pair at 2 FLOPs
    assert flops == 80 * 3 * 1536 * 512 * 2
    # 40 experts x 3 matrices of 1536 x 512, plus per pair and projection
    # a 1536-wide row and a 512-wide row, all bf16
    assert nbytes == 2 * (40 * 3 * 1536 * 512 + 80 * 3 * (1536 + 512))
    assert cost.TRACE_NAMES == ("grouped_matmul",)
    from repro.kernels.grouped_matmul.kernel import TRACE_NAME
    assert TRACE_NAME in cost.TRACE_NAMES


def test_gmm_reader_counts_the_kernel_not_its_consumers():
    """The roofline reader times the ops the kernel's name heads, not the
    fusions that read its output as an operand."""
    reader = _load(BENCH / "metrics" / "moe.gmm_roofline.rebind-2k.py")
    dev = "/device:TPU:0"
    rows = [
        [dev, "XLA Ops", "%grouped_matmul.36 = f32[8,4] custom-call(%a)",
         0.0, 300.0],
        [dev, "XLA Ops", "%grouped_matmul = f32[8,4] custom-call(%b)",
         400.0, 100.0],
        [dev, "XLA Ops", "%fusion.9 = bf16[8,4] fusion(f32[8,4] "
         "%grouped_matmul.36)", 300.0, 50.0],
        ["/host:CPU", "python", "grouped_matmul", 0.0, 900.0],
    ]
    assert reader.kernel_s(rows, ("grouped_matmul",)) == pytest.approx(
        400e-9)


# --------------------------------------------------------------------------
# a traced granite rebind cell, added as files and entries only
# --------------------------------------------------------------------------

CELL = "granite-smoke.rebind"
NEW_METRICS = ("moe.gmm_roofline.rebind-2k", "bind.weights_ms.rebind-2k",
               "bind.admit_ms.rebind-2k", "engine.tick_ms.rebind-2k",
               "device.idle.rebind-2k")


@pytest.fixture
def granite_root(tmp_path):
    """A checkout with the benchmark as committed plus a CPU-sized granite
    rebind cell: its config, traffic and cell files, its entries, and the
    cell appended to the per-layer metrics the granite cell reports."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp_path / "src")
    for sub, name in (("configs", "granite-smoke.json"),
                      ("traffic", "rebind-granite.json"),
                      ("cells", f"{CELL}.json")):
        shutil.copy(FIXTURE / sub / name, tmp_path / "bench" / sub / name)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "granite-smoke", "source": "tests",
                            "file": "bench/configs/granite-smoke.json",
                            "reduced": [], "why": "tiny"})
    spec["workloads"].append({"name": CELL, "config": "granite-smoke",
                              "traffic": "rebind-granite", "chips": 1,
                              "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in NEW_METRICS + ("bind_ttft_ms",):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_granite_cell_traced_reports_its_metrics(granite_root, monkeypatch,
                                                 capsys):
    """Traced on the CPU: correct against the reference, and the span
    metrics read; the device-trace metrics read where the CPU trace has
    device ops and are left out (None) where it has none."""
    run = _load(granite_root / "bench" / "run.py")
    monkeypatch.setattr(run, "enable_cache", lambda _root: None)
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 23),
                   "--seconds", "1.5", "--trace", "1"], root=granite_root,
                  chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    res = json.loads(out[-1])
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    got = res["metrics"]
    assert set(got) <= set(NEW_METRICS)
    for name in ("bind.weights_ms.rebind-2k", "bind.admit_ms.rebind-2k",
                 "engine.tick_ms.rebind-2k"):
        assert got[name]["value"] > 0 and got[name]["unit"] == "ms"
    for name in ("moe.gmm_roofline.rebind-2k", "device.idle.rebind-2k"):
        if name in got:
            assert 0 <= got[name]["value"] <= 100
