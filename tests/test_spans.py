"""The program's ``repro.*`` spans in a profiler trace, and what a rebind
compiles.

Two serve tasks of different shape go through ``ClusterSim`` -> pilot ->
image -> engine at test size under ``jax.profiler.trace``, one task at a
time; the trace is read back with ``ProfileData``, whose events carry the
spans' ids as stats.  A third task binds the first image again with JAX's
compile events counted.
"""

from __future__ import annotations

import collections
import glob
import os
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))

from benchkit.loads import CompileMeter  # noqa: E402

# the spans of one bind, in the order a bind opens them, and the id each
# carries ("step" spans repeat per tick and are checked apart)
BIND_PATH = [
    ("repro.repo.submit", "task_id"),
    ("repro.latebind.pull", "task_id"),
    ("repro.pilot.stage", "task_id"),
    ("repro.payload.weights", "task_id"),
    ("repro.payload.engine_build", "task_id"),
    ("repro.engine.admit", "rid"),
    ("repro.pilot.finish", "task_id"),
]


def _span_events(trace_dir) -> list[tuple]:
    """Every ``repro.*`` event: ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return sorted(
        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
          {k: v for k, v in ev.stats})
         for plane in ProfileData.from_file(path).planes
         for line in plane.lines for ev in line.events
         if ev.name.startswith("repro.")), key=lambda e: e[1])


def _bind(sim, pilot, image, req) -> tuple[int, dict]:
    """Submit one serve task and wait for its result and the pilot's
    history record (the finish span closes after it)."""
    tid = sim.repo.submit(image, n_steps=1 << 10, max_wall=300.0,
                          max_attempts=1, env={"seed": 5},
                          payload_spec={"trace": [req]})
    end = time.monotonic() + 300.0
    while time.monotonic() < end:
        res = sim.repo.result(tid)
        if res is not None and any(r.get("task_id") == tid
                                   for r in pilot.history):
            return tid, res.telemetry
        time.sleep(0.002)
    raise AssertionError(f"task {tid} did not finish: {pilot.history}")


@pytest.fixture(scope="module")
def rebind(tmp_path_factory):
    from repro.core.cluster import ClusterSim
    from repro.core.images import PayloadImage
    from repro.core.pilot import PilotConfig

    images = [PayloadImage("smollm-360m", "custom:64x2", "serve"),
              PayloadImage("smollm-360m", "custom:32x1", "serve")]
    reqs = [{"rid": 100 + i, "prompt": list(range(3 + i, 19 + i)),
             "max_new_tokens": 4 + i} for i in range(3)]
    sim = ClusterSim()
    (slice_,) = sim.provision(1)
    pilot = sim.spawn_pilot(slice_, PilotConfig(max_payloads=1 << 10,
                                                idle_grace=120.0))
    out = str(tmp_path_factory.mktemp("spans"))
    try:
        with jax.profiler.trace(out):
            tasks = [_bind(sim, pilot, img, req)
                     for img, req in zip(images, reqs)]
        meter = CompileMeter()
        try:
            meter.take()
            again = _bind(sim, pilot, images[0], reqs[2])
            compiles = meter.take()
        finally:
            jax.monitoring.unregister_event_duration_listener(meter._on_event)
    finally:
        pilot.drain()
        sim.repo.kick()
        sim.join_all(timeout=60.0)
    return {"events": _span_events(out), "tasks": tasks, "reqs": reqs,
            "again": again, "compiles": compiles}


def test_each_bind_path_span_once_per_task_in_order(rebind):
    events = rebind["events"]
    for (tid, _), req in zip(rebind["tasks"], rebind["reqs"]):
        ids = {"task_id": tid, "rid": req["rid"]}
        starts = []
        for name, key in BIND_PATH:
            mine = [e for e in events
                    if e[0] == name and e[3].get(key) == ids[key]]
            assert len(mine) == 1, (name, tid, mine)
            starts.append(mine[0][1])
        assert starts == sorted(starts), tid
    names = collections.Counter(e[0] for e in events)
    for name, _ in BIND_PATH:
        assert names[name] == len(rebind["tasks"]), name


def test_tick_spans_pair_with_decode_steps(rebind):
    """One decode and one readback span per decode step, numbered by the
    engine's step counter; no draft span with speculation off."""
    events = rebind["events"]
    steps = [tel["serve"]["decode_steps"] for _, tel in rebind["tasks"]]
    assert all(n > 0 for n in steps)
    for name in ("repro.engine.decode", "repro.engine.readback"):
        got = sorted(e[3]["step"] for e in events if e[0] == name)
        assert got == sorted(s for n in steps for s in range(n)), name
    assert not [e for e in events if e[0] == "repro.engine.draft"]
    # each readback follows its own step's dispatch
    dec = [e for e in events if e[0] == "repro.engine.decode"]
    back = [e for e in events if e[0] == "repro.engine.readback"]
    assert all(b[1] >= d[2] for d, b in zip(dec, back))


def test_spans_leave_the_served_tokens_and_transfers_alone(rebind):
    for (_, tel), req in zip(rebind["tasks"], rebind["reqs"]):
        toks = tel["tokens"][str(req["rid"])]
        # the admission's token, then one per decode step
        assert len(toks) == req["max_new_tokens"] + 1
        serve = tel["serve"]
        assert serve["d2h_transfers"] == serve["decode_steps"]
        assert "draft_overhead_s" not in serve


def test_second_bind_of_a_bound_image_compiles_nothing(rebind):
    _, tel = rebind["again"]
    req = rebind["reqs"][2]
    assert len(tel["tokens"][str(req["rid"])]) == req["max_new_tokens"] + 1
    assert rebind["compiles"] == 0
    # the staged weights init compiled in each image's first bind (no
    # warm() on this path), and not again
    assert [t["weights_compiles"] for _, t in rebind["tasks"]] == [1, 1]
    assert tel["weights_compiles"] == 0


def test_spec_draft_is_a_span_and_no_host_sync(tmp_path, monkeypatch):
    """A speculative step dispatches its draft chain inside the draft
    span, and nothing waits on the drafts before verify."""
    from repro.configs.base import get_smoke_config
    from repro.models.api import build_model
    from repro.serving.engine import Request, ServeEngine

    cfg = get_smoke_config("smollm-360m")
    bundle = build_model(cfg)
    eng = ServeEngine(cfg, bundle.init(jax.random.key(0)), slots=2,
                      max_len=64, bundle=bundle, spec="draft", spec_k=2)
    assert eng.spec == "draft"
    for i in range(2):
        eng.submit(Request(rid=i, prompt=(np.arange(9) + i + 1).astype(
            np.int32), max_new_tokens=6))
    syncs = []
    wait = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or wait(x))
    with jax.profiler.trace(str(tmp_path)):
        stats = eng.run()
    counts = collections.Counter(e[0] for e in _span_events(str(tmp_path)))
    assert eng.steps > 0 and syncs == []
    assert counts["repro.engine.draft"] == eng.steps
    assert counts["repro.engine.decode"] == eng.steps
    assert counts["repro.engine.admit"] == 2
    assert "draft_overhead_s" not in stats
