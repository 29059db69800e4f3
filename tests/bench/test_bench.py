"""The benchmark's own tests, on the CPU at CPU sizes.

They cover the trace reduction (on a trace recorded on the chip and on
hand-made events), the cost functions against hand counts, the traffic
generator, discovery of a cell added as files only, the comparison that
decides ``correct`` (against faults planted in the timed path, and the
fp8 control), and ``run.py`` refusing to run off the chip.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(BENCH))

from benchkit import compare, reference, trace, traffic  # noqa: E402


def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "t_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

def test_trace_reduction_hand_events():
    dev, host = "/device:TPU:0", "/host:CPU"
    rows = [
        [dev, "XLA Ops", "fusion.1", 0.0, 100.0],
        [dev, "XLA Ops", "_paged_kernel", 50.0, 120.0],    # overlaps
        [dev, "XLA Ops", "_attn_kernel", 400.0, 100.0],
        [dev, "XLA Modules", "jit_body", 0.0, 500.0],      # not an op
        [host, "python", "PjitFunction(body)", 140.0, 30.0],
        [host, "python", "device_get", 160.0, 230.0],
    ]
    assert trace.busy_s(rows) == pytest.approx(270e-9)
    assert trace.kernel_s(rows, ("_paged_kernel",)) == pytest.approx(1.2e-7)
    assert trace.kernel_s(rows, ("absent",)) == 0.0
    (name, gap), = trace.idle_gaps(rows)
    assert name == "device_get" and gap == pytest.approx(230e-9)
    assert trace.top_ops(rows, 1) == [["_paged_kernel", 1.2e-7]]


def _union_len(intervals):
    """Length of a union of intervals, by sweeping sorted end points."""
    pts = sorted([(s, 1) for s, _ in intervals]
                 + [(e, -1) for _, e in intervals])
    depth, last, tot = 0, None, 0.0
    for x, d in pts:
        if depth > 0:
            tot += x - last
        depth += d
        last = x
    return tot


def test_trace_reduction_recorded_chip_trace():
    """20 ms of a trace recorded on one TPU v5e in a rebind run."""
    rows = json.loads((FIXTURE / "trace_rebind.json").read_text())
    ops = trace.device_ops(rows)
    assert list(ops) == ["/device:TPU:0"]
    evs = ops["/device:TPU:0"]
    busy = _union_len([(s, s + d) for _, s, d in evs]) / 1e9
    assert trace.busy_s(rows) == pytest.approx(busy, rel=1e-9)
    assert 0 < busy <= (max(s + d for _, s, d in evs)
                        - min(s for _, s, _ in evs)) / 1e9
    gaps = trace.idle_gaps(rows)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    total = sum(d for _, _, d in evs) / 1e9
    assert sum(v for _, v in trace.top_ops(rows, 10 ** 6)) == pytest.approx(
        total, rel=1e-9)
    # the paged decode kernel is found by its stable name
    paged = _load(BENCH / "costs" / "paged_decode.py").TRACE_NAMES
    assert 0 < trace.kernel_s(rows, paged) < busy


def test_trace_reduction_averages_device_planes():
    """Two chips: busy and kernel time are per-chip averages, a plane
    without an ``XLA Ops`` line counts every line, and the idle gaps are
    read on the first plane."""
    rows = [
        ["/device:TPU:0", "XLA Ops", "_attn_kernel", 0.0, 300.0],
        ["/device:TPU:0", "XLA Ops", "fusion.2", 500.0, 100.0],
        ["/device:TPU:1", "Ops", "_attn_kernel", 0.0, 100.0],
        ["/device:TPU:1", "Ops", "fusion.2", 50.0, 100.0],
        ["/device:CPU:0", "XLA Ops", "host_op", 0.0, 320.0],    # not a chip
        ["/host:CPU", "python", "bind", 250.0, 300.0],
    ]
    assert trace.busy_s(rows) == pytest.approx((400.0 + 150.0) / 2 * 1e-9)
    assert trace.kernel_s(rows, ("_attn_kernel",)) == pytest.approx(
        (300.0 + 100.0) / 2 * 1e-9)
    assert trace.idle_gaps(rows) == [["bind", pytest.approx(200e-9)]]
    total = sum(v for _, v in trace.top_ops(rows, 10))
    assert total == pytest.approx((400.0 + 200.0) / 2 * 1e-9)


# --------------------------------------------------------------------------
# cost functions
# --------------------------------------------------------------------------

SMOLLM = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())[
    "model"]
GRANITE = json.loads((BENCH / "configs"
                      / "granite-moe-3b-a800m-16l.json").read_text())["model"]


def test_flash_prefill_cost_hand_count():
    cost = _load(BENCH / "costs" / "flash_prefill.py")
    flops, nbytes = cost.cost(SMOLLM, 4)
    # 15 heads x 64 dims; causal pairs for 4 tokens: 4+3+2+1 = 10; QK^T and
    # PV each 2 FLOPs per multiply-add
    assert flops == 15 * 64 * 10 * 2 * 2
    # q and o: 4 x 15 x 64, k and v: 4 x 5 x 64, two bytes each
    assert nbytes == 2 * (2 * 4 * 15 * 64 + 2 * 4 * 5 * 64)


def test_paged_decode_cost_hand_count():
    cost = _load(BENCH / "costs" / "paged_decode.py")
    flops, nbytes = cost.cost(GRANITE, 100)
    assert flops == 24 * 64 * 100 * 4            # head_dim 1536 / 24
    assert nbytes == 2 * (100 * 8 * 64 * 2 + 24 * 64 * 2)


def test_lm_forward_cost_hand_count():
    lm = _load(BENCH / "costs" / "lm_forward.py")
    per_layer = (960 * 15 * 64 * 2 + 960 * 5 * 64 * 2      # q, o, k, v
                 + 3 * 960 * 2560)                          # gated FFN
    want = 2 * (32 * per_layer + 960 * 49152)               # + LM head
    assert lm.weight_flops_per_token(SMOLLM) == want
    moe = (1536 * 24 * 64 * 2 + 1536 * 8 * 64 * 2
           + 1536 * 40 + 8 * 3 * 1536 * 512)                # router + top-8
    assert lm.weight_flops_per_token(GRANITE) == 2 * (16 * moe
                                                      + 1536 * 49155)
    # 3 tokens attend over 1, 2 and 3 positions
    assert lm.sequence_flops(SMOLLM, 3) == (
        3 * want + 2 * 2 * 15 * 64 * 6 * 32)


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def _mix(name):
    """A mix of the benchmark's, else one of the test fixture's."""
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        path = FIXTURE / "traffic" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name", ["chat-lognormal", "batch-uniform",
                                  "rebind"])
def test_traffic_deterministic_and_same_work_per_seed(name):
    mix = _mix(name)
    a = traffic.requests(mix, 2 ** 31 + 11, 64, 49152)
    assert a == traffic.requests(mix, 2 ** 31 + 11, 64, 49152)
    b = traffic.requests(mix, 12, 64, 49152)
    assert a != b
    lens = lambda rs: sorted((len(r["prompt"]), r["output_len"]) for r in rs)
    assert sorted(len(r["prompt"]) for r in a) == sorted(
        len(r["prompt"]) for r in b)
    assert sorted(r["output_len"] for r in a) == sorted(
        r["output_len"] for r in b)
    assert lens(a) and all(r["max_new_tokens"] == r["output_len"] - 1
                           for r in a)


def test_traffic_lengths_follow_the_mix_not_the_buckets():
    mix = _mix("chat-lognormal")
    plens = traffic.lengths(mix["prompt_len"], 400)
    assert plens.min() >= 32 and plens.max() <= 1536
    assert abs(float(np.median(plens)) - 384) <= 8
    pow2 = {1 << k for k in range(4, 12)}
    assert np.mean([p in pow2 for p in plens]) < 0.05
    assert len(set(plens.tolist())) > 200


def test_traffic_schedule_rate_and_seeded_order():
    mix = _mix("chat-lognormal")
    a = traffic.schedule(mix, 5, 100.0)
    assert np.array_equal(a, traffic.schedule(mix, 5, 100.0))
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(len(a) - rate * 100) <= 0.02 * rate * 100
    b = traffic.schedule(mix, 6, 100.0)
    assert not np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[0] == 0.0


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------

def test_reference_matches_program_at_small_size():
    """The reference's weights are the serve image's weights, and its
    logits agree with the program's prefill to bf16 rounding."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.models.api import build_model
    cfg = json.loads((FIXTURE / "configs" / "smollm-smoke.json").read_text())
    m = reference.Model.from_config(cfg["model"])
    bundle = build_model(get_smoke_config("smollm-360m"))
    seed = 2 ** 31 + 3
    params = bundle.init(jax.random.key(seed))
    _, _, keys = reference.root_keys(seed, m.layers)
    w = reference.layer_weights(m, keys[1])
    got = jax.tree.map(lambda a: a[1], params["layers"][0])
    for k in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(w[k], got["mixer"][k])
    for k in ("up", "down", "gate"):
        np.testing.assert_array_equal(w[k], got["ffn"][k])
    toks = np.random.default_rng(0).integers(0, m.vocab, (1, 48))
    logits, _ = jax.jit(bundle.prefill)(params, {"tokens": jnp.asarray(toks)})
    ref = reference.forward(m, seed, toks)
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(ref[0, -1]), atol=0.02)


def test_sample_keeps_the_longest():
    reqs = [{"rid": i, "prompt": [0] * (i + 1), "tokens": [1, 2],
             "failed": False} for i in range(20)]
    picked = compare.sample(reqs, 5, seed=3)
    assert len(picked) == 5 and picked[0]["rid"] == 19
    assert compare.sample(reqs, 5, seed=3) == picked


# --------------------------------------------------------------------------
# whole runs at CPU size: discovery by name, faults, refusal off the chip
# --------------------------------------------------------------------------

NEW_METRIC = '''"""A metric a later change adds as a file: tokens served per bind."""


def read(ctx):
    binds = [b for b in ctx.run.binds if not b["failed"]]
    return sum(len(b["tokens"]) for b in binds) / len(binds) if binds else None
'''


@pytest.fixture
def fixture_root(tmp_path):
    """A checkout with the benchmark as committed plus a CPU-sized cell
    added the way a later change adds one: files and entries only."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp_path / "src")
    for sub in ("configs", "traffic", "cells"):
        for f in (FIXTURE / sub).iterdir():
            shutil.copy(f, tmp_path / "bench" / sub / f.name)
    (tmp_path / "bench" / "metrics" / "tokens_per_bind.py").write_text(
        NEW_METRIC)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    extra = json.loads((FIXTURE / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[key] += extra[key]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def _run_cell(root, monkeypatch, capsys, cell, seed=2 ** 31 + 21, trace=0):
    run = _load(root / "bench" / "run.py")
    monkeypatch.setattr(run, "enable_cache", lambda _root: None)
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "1.5", "--trace", str(trace)], root=root, chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_cell_added_as_files_is_found_and_correct(fixture_root, monkeypatch,
                                                  capsys):
    res = _run_cell(fixture_root, monkeypatch, capsys, "smoke.rebind")
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 2 == 0            # whole rounds of 2 images
    assert set(res["metrics"]) == {"bind_ttft_ms", "setup_s"}
    assert res["metrics"]["bind_ttft_ms"]["value"] > 0
    assert list(res)[-1] == "checks"
    res = _run_cell(fixture_root, monkeypatch, capsys, "smoke.rebind",
                    trace=1)
    assert res["metrics"]["tokens_per_bind"]["value"] == 5
    assert "bind.pull_ms.rebind" in res["metrics"]


def test_each_bind_reads_its_own_history_record(fixture_root, monkeypatch):
    """The pilot completes a task before it cleans up and appends the
    task's history record; with the clean-up slowed, a bind that read the
    newest record would read the previous task's.  Each bind's pull time
    is the ``bind_seconds`` of its own task."""
    from repro.core import cluster, latebind
    pilots = []
    spawn = cluster.ClusterSim.spawn_pilot

    def spy(self, *a, **kw):
        pilots.append(spawn(self, *a, **kw))
        return pilots[-1]

    reset = latebind.PayloadExecutor.reset

    def slow_reset(self, *a, **kw):
        time.sleep(0.05)
        return reset(self, *a, **kw)

    monkeypatch.setattr(cluster.ClusterSim, "spawn_pilot", spy)
    monkeypatch.setattr(latebind.PayloadExecutor, "reset", slow_reset)
    run_py = _load(fixture_root / "bench" / "run.py")
    spec = run_py.load_cell(fixture_root, "smoke.rebind")
    from benchkit import loads
    run = loads.rebind(spec["cfg"], spec["mix"], 2 ** 31 + 9, 1.0,
                       loads.CompileMeter(), None)
    own = {rec["task_id"]: rec["bind_seconds"] for rec in pilots[0].history}
    assert len(run.binds) >= 2
    assert [b["pull_s"] for b in run.binds] == [own[b["task_id"]]
                                                for b in run.binds]


@pytest.mark.parametrize("cell, metrics", [
    ("smoke.chat", {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}),
    ("smoke.batch", {"output_tokens_per_s", "setup_s"}),
])
def test_loop_cells_run_through_the_fleet_and_are_correct(
        fixture_root, monkeypatch, capsys, cell, metrics):
    """The open and the closed loop drive ``FleetDispatcher.submit``
    through one pilot's serve payload; every request they send is
    answered and agrees with the reference.

    The pilot's straggler check is off here: with one server it compares
    the server's step times with a faster average of the same times and
    kills it after an admission tick (PERF.md, Open questions), which is
    the program's fault to mend and not what this test is about."""
    import functools
    from repro.core import monitor, pilot
    monkeypatch.setattr(pilot, "MonitorLimits", functools.partial(
        monitor.MonitorLimits, straggler_factor=math.inf))
    res = _run_cell(fixture_root, monkeypatch, capsys, cell)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == metrics
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["checks"]["missing_answers"]["value"] == 0


def _plant(monkeypatch, fault):
    """Break the engine's timed decode step underneath the whole path:
    the step the serve image compiles is this one from then on."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine

    def broken(bundle, max_len, mesh=None):
        def body(params, state, active, budget):
            _, new_state = bundle.decode(params, state)
            tok = new_state["token"][:, 0]
            if fault == "state_unchanged":
                new_state = state
            else:                                   # a token altered
                tok = (tok + 1) % bundle.cfg.vocab_size
                new_state = {**new_state, "token": tok[:, None]}
            budget = budget - active.astype(jnp.int32)
            done = active & ((budget <= 0) | (new_state["pos"] >= max_len))
            packed = jnp.stack([tok, done.astype(jnp.int32)])
            return packed, new_state, active & ~done, budget
        return jax.jit(body)

    monkeypatch.setattr(engine, "make_engine_step", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_planted_fault_is_not_correct(fixture_root, monkeypatch, capsys,
                                      fault):
    _plant(monkeypatch, fault)
    res = _run_cell(fixture_root, monkeypatch, capsys, "smoke.rebind")
    assert res["correct"] is False
    gap = res["checks"]["served_gap"]
    assert gap["value"] > gap["limit"]


def test_fp8_control_fails_where_the_program_passes(fixture_root):
    """At a size a test can hold: the program's served tokens pass the
    cell's limit, and the fp8 control, read over the same prompts and
    positions, fails it."""
    run_py = _load(fixture_root / "bench" / "run.py")
    spec = run_py.load_cell(fixture_root, "small.rebind")
    from benchkit import loads
    served, control = [], []
    for seed in (2 ** 31 + 5, 6):
        run = loads.rebind(spec["cfg"], spec["mix"], seed, 1.0,
                             loads.CompileMeter(), None)
        ok, numbers, readings = compare.judge(spec["cfg"], spec["check"],
                                              run, seed, control=True)
        assert ok
        served.append(numbers["served_gap"][0])
        control.append(max(r["control"] for r in readings))
    limit = spec["check"]["served_gap_limit"]
    assert max(served) < limit < min(control)


def test_run_refuses_off_the_chip(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    args = ["--workload", "smollm-360m.rebind", "--seed", str(2 ** 31 + 1),
            "--seconds", "1", "--trace", "0"]
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), *args],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a directory holding only BENCHMARK.json and the benchmark's files
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run([sys.executable, str(tmp_path / "bench" / "run.py"),
                        *args], capture_output=True, text=True, env=env,
                       cwd=tmp_path, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
