"""The readers of the program's ``repro.*`` spans (``benchkit/spans.py`` and
the five ``bench/metrics`` files that use it), on hand-made rows, on rows
recorded on the chip, and in a traced run of a CPU-sized rebind cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
FIXTURE = pathlib.Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(BENCH))

from benchkit import spans, trace  # noqa: E402
from benchkit.readers import Ctx  # noqa: E402

READERS = ["bind.control_ms.rebind", "bind.weights_ms.rebind",
           "bind.admit_ms.rebind", "engine.tick_ms.rebind",
           "device.idle_unspanned.rebind"]


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _read(name: str, rows):
    return _reader(name)(Ctx(run=None, model={}, mix={}, peaks={},
                             bench_dir=BENCH, rows=rows))


DEV, HOST = "/device:TPU:0", "/host:CPU"


def _hand_rows():
    """Two binds and the start of a third that never reached its payload,
    on three host threads, over a chip that is busy five times."""
    def span(line, name, start, dur):
        return [HOST, line, f"repro.{name}", float(start), float(dur)]
    return [
        span("client", "repo.submit", 0, 10),
        span("pilot", "latebind.pull", 100, 5),
        span("pilot", "pilot.stage", 110, 50),
        span("payload", "payload.weights", 200, 1000),
        span("payload", "payload.engine_build", 1250, 100),
        span("payload", "engine.admit", 1400, 300),
        span("payload", "engine.decode", 1750, 20),
        span("payload", "engine.readback", 1780, 200),
        span("payload", "engine.decode", 2000, 10),
        span("payload", "engine.readback", 2020, 100),
        span("pilot", "pilot.finish", 2200, 50),
        span("client", "repo.submit", 3000, 10),
        span("payload", "payload.weights", 3300, 800),
        span("payload", "engine.admit", 4200, 200),
        span("payload", "engine.decode", 4500, 10),
        span("payload", "engine.readback", 4520, 80),
        span("client", "repo.submit", 6000, 10),
        [HOST, "payload", "PjitFunction(scatter)", 4150.0, 500.0],
        [DEV, "XLA Ops", "fusion.1", 0.0, 50.0],
        [DEV, "XLA Ops", "fusion.2", 300.0, 600.0],
        [DEV, "XLA Ops", "fusion.3", 1500.0, 100.0],
        [DEV, "XLA Ops", "fusion.4", 2100.0, 50.0],
        [DEV, "XLA Ops", "fusion.5", 5000.0, 100.0],
        [DEV, "XLA Modules", "jit_step", 0.0, 5100.0],     # not an op
    ]


def _unspanned_by_counting(rows) -> float:
    """The unspanned idle share by testing every nanosecond between the
    chip's first and last operation."""
    ops = [(st, st + d) for p, ln, _, st, d in rows
           if p == DEV and ln == "XLA Ops"]
    covered = [(st, st + d) for p, _, n, st, d in rows
               if p == HOST and n.startswith("repro.")]
    idle = bare = 0
    for t in range(int(min(s for s, _ in ops)), int(max(e for _, e in ops))):
        if any(s <= t < e for s, e in ops):
            continue
        idle += 1
        bare += not any(s <= t < e for s, e in covered)
    return 100.0 * bare / idle


def test_readers_on_hand_made_rows():
    rows = _hand_rows()
    ms = 1e-6
    # submit -> weights start; the third submit has no payload and is out
    assert _read("bind.control_ms.rebind", rows) == pytest.approx(
        (200 + 300) / 2 * ms)
    assert _read("bind.weights_ms.rebind", rows) == pytest.approx(
        (1000 + 800) / 2 * ms)
    assert _read("bind.admit_ms.rebind", rows) == pytest.approx(
        (300 + 200) / 2 * ms)
    # decode start -> the end of the readback after it, per tick
    assert _read("engine.tick_ms.rebind", rows) == pytest.approx(
        (230 + 120 + 100) / 3 * ms)
    share = _read("device.idle_unspanned.rebind", rows)
    assert share == pytest.approx(_unspanned_by_counting(rows))
    assert 0 < share < 100


def test_pairing_skips_a_head_without_its_tail():
    """A span whose partner never came is left out, and does not steal
    the next head's partner."""
    rows = [[HOST, "c", "repro.a", 0.0, 1.0],
            [HOST, "c", "repro.a", 10.0, 1.0],
            [HOST, "p", "repro.b", 15.0, 5.0],
            [HOST, "c", "repro.a", 30.0, 1.0]]
    assert spans.paired(rows, "repro.a", "repro.b", to_end=False) == [
        (10.0, 15.0)]
    assert spans.paired(rows, "repro.a", "repro.b", to_end=True) == [
        (10.0, 20.0)]


def test_unspanned_share_takes_the_union_of_spans():
    """Spans that overlap on two threads count once; a host event that is
    not a span names nothing."""
    rows = [[DEV, "XLA Ops", "a", 0.0, 10.0],
            [DEV, "XLA Ops", "b", 110.0, 10.0],
            [HOST, "t1", "repro.x", 20.0, 40.0],
            [HOST, "t2", "repro.y", 40.0, 40.0],
            [HOST, "t3", "PjitFunction(f)", 0.0, 200.0]]
    # idle 10..110 (100 ns), spanned 20..80 (60 ns)
    assert spans.unspanned_idle_share(rows) == pytest.approx(40.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_are_none_without_spans(name):
    """On a trace of a program without spans (recorded on the chip), on
    hand-made rows with the spans taken out, and without a trace."""
    recorded = json.loads((FIXTURE / "trace_rebind.json").read_text())
    assert not [r for r in recorded if r[2].startswith("repro.")]
    bare = [r for r in _hand_rows() if not r[2].startswith("repro.")]
    assert _read(name, recorded) is None
    assert _read(name, bare) is None
    assert _read(name, None) is None


def test_unspanned_share_is_none_without_a_chip_plane():
    rows = [r for r in _hand_rows() if r[0] == HOST]
    assert _read("bind.weights_ms.rebind", rows) is not None
    assert _read("device.idle_unspanned.rebind", rows) is None


def test_readers_on_a_recorded_chip_trace():
    """About 20 ms of a traced rebind run on one TPU v5e, from just before
    a task's submit, with that task's spans up to its first token kept
    whole (``trace_rebind_spans.json``)."""
    rows = json.loads((FIXTURE / "trace_rebind_spans.json").read_text())
    assert list(trace.device_ops(rows)) == ["/device:TPU:0"]
    got = {name: _read(name, rows) for name in READERS}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["bind.control_ms.rebind"] < got["bind.weights_ms.rebind"]
    assert got["bind.admit_ms.rebind"] > 0
    assert got["engine.tick_ms.rebind"] > 0
    assert 0 <= got["device.idle_unspanned.rebind"] <= 100
    # the longest idle gaps are named by the program's spans
    names = [n for n, _ in trace.idle_gaps(rows)]
    assert any(n.startswith("repro.") for n in names), names


def test_traced_cpu_run_reports_the_span_metrics(tmp_path, monkeypatch,
                                                 capsys):
    """A CPU-sized rebind cell run with ``--trace 1``, the five metrics
    listed for it: the program's spans reach the result line.  The CPU
    has no chip plane, so the idle share is left out."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", tmp_path / "src")
    for sub in ("configs", "traffic", "cells"):
        for f in (FIXTURE / sub).iterdir():
            shutil.copy(f, tmp_path / "bench" / sub / f.name)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    extra = json.loads((FIXTURE / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end"):
        spec[key] += extra[key]
    spec["per_layer"] = [{**m, "workloads": ["smoke.rebind"]}
                         for m in spec["per_layer"] if m["name"] in READERS]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    spec_run = importlib.util.spec_from_file_location(
        "t_span_run", tmp_path / "bench" / "run.py")
    run = importlib.util.module_from_spec(spec_run)
    spec_run.loader.exec_module(run)
    monkeypatch.setattr(run, "enable_cache", lambda _root: None)
    rc = run.main(["--workload", "smoke.rebind", "--seed", str(2 ** 31 + 77),
                   "--seconds", "1.5", "--trace", "1"], root=tmp_path,
                  chip=False)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(READERS) - {"device.idle_unspanned.rebind"}
    assert all(v > 0 for v in got.values()), got
