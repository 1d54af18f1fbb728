"""The program-span reduction, on hand-made events and on a trace of the
engine recorded on the CPU (whose XLA operations stand in for a chip's)."""
import chipbench_paths  # noqa: F401  (first: the import path)
import jax
import numpy as np
import pytest

import program_spans as ps
import trace_reduce as tr
from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import build_octree
from repro.engine import CollisionEngine, EngineConfig, plan_queries

ns = 1e-9


def _ev(name, s, e, text=None):
    return (name, text or name, float(s), float(e))


#: The hand-made window of ``test_trace_reduce``.
DEVICE = [[_ev("a", 10, 20), _ev("b", 15, 30), _ev("c", 50, 60),
           _ev("fusion.3", 70, 80, "fusion.3 kernel=persist_kernel"),
           _ev("late", 95, 120)]]
HOST = [[_ev("bench.window", 0, 100), _ev("bench.execute", 5, 35),
         _ev("bench.execute", 45, 85)],
        [_ev("work", 30, 50), _ev("inner", 32, 48)]]
#: One engine call inside each bench.execute, on the first host thread,
#: and a runtime event on a thread of its own.
PROGRAM = [_ev("engine.execute", 6, 34), _ev("executor.stage", 6, 9),
           _ev("executor.dispatch", 9, 11), _ev("executor.sync", 11, 31),
           _ev("executor.sync", 31, 33),
           _ev("engine.execute", 46, 84), _ev("executor.stage", 46, 48),
           _ev("executor.dispatch", 48, 49), _ev("executor.sync", 49, 82)]


def _with_program():
    return [HOST[0] + PROGRAM, HOST[1], [_ev("Transpose", 7, 10)]]


def test_existing_outputs_unchanged_by_program_spans():
    """busy, kernel time, the bench spans and the device operations read
    the same with the program's spans in the host plane."""
    base = tr.reduce_events(DEVICE, HOST, {"persist": "persist_kernel"})
    got = tr.reduce_events(DEVICE, _with_program(),
                           {"persist": "persist_kernel"})
    for k in ("window_s", "busy_s", "kernel_s", "kernel_events", "spans",
              "device_ops"):
        assert got[k] == base[k], k
    assert set(got["spans"]) == {"bench.execute"}
    # idle_gaps names the innermost host event of a gap; a program span
    # can now be that event, so only the gaps' total is the same.
    assert sum(v for _, v in got["idle_gaps"]) == pytest.approx(
        sum(v for _, v in base["idle_gaps"]))


def test_reduce_program_hand_made():
    out = ps.reduce_program(DEVICE, _with_program())
    spans = out["spans"]
    assert set(spans) == {"engine.execute", "executor.stage",
                          "executor.dispatch", "executor.sync"}
    assert len(spans["engine.execute"]) == 2
    # device busy: [10, 30] + [50, 60] + [70, 80] + [95, 100]
    assert [b for _, _, b in spans["executor.sync"]] == pytest.approx(
        [19 * ns, 0.0, 20 * ns])
    assert [b for _, _, b in spans["executor.dispatch"]] == pytest.approx(
        [1 * ns, 0.0])
    phases = dict(out["idle_by_phase"])
    # idle: [0, 10], [30, 50], [60, 70], [80, 95]
    assert sum(phases.values()) == pytest.approx(55 * ns)
    assert phases["executor.stage"] == pytest.approx(3 * ns + 2 * ns)
    assert phases["executor.dispatch"] == pytest.approx(1 * ns + 1 * ns)
    assert phases["executor.sync"] == pytest.approx(
        1 * ns + 2 * ns + 1 * ns + 10 * ns + 2 * ns)
    # [33, 34] and [82, 84]: the call, past its last sync
    assert phases["engine.execute"] == pytest.approx(1 * ns + 2 * ns)
    # before the first call, between the calls, after the second
    assert phases[ps.OUTSIDE] == pytest.approx((6 + 12 + 11) * ns)
    assert ps.stage_ms(spans) == pytest.approx((3 + 2) * ns / 2 * 1e3)
    assert ps.sync_ms(spans) == pytest.approx(
        ((20 - 19) + 2 + (33 - 20)) * ns / 2 * 1e3)
    assert ps.launch_host_ms(spans) is None


def test_by_phase_takes_the_deepest_span():
    spans = ps.program_spans(
        [[_ev("bench.window", 0, 100), _ev("batcher.launch", 10, 90),
          _ev("batcher.launch", 20, 40), _ev("engine.execute", 22, 38),
          _ev("executor.sync", 30, 38)],
         [_ev("batcher.submit", 35, 50)]], 0, 100)
    depth = {(n, s): d for n, s, _, _, d in spans}
    assert depth[("batcher.launch", 10)] == 0
    assert depth[("executor.sync", 30)] == 3
    assert depth[("batcher.submit", 35)] == 0
    got = ps.by_phase([(0, 100)], spans, 0, 100)
    assert got[ps.OUTSIDE] == pytest.approx(20)
    assert got["executor.sync"] == pytest.approx(8)
    assert got["engine.execute"] == pytest.approx(8)
    # [38, 40] lies in the inner launch and in the submit: the launch is
    # deeper; [40, 50] in the outer launch and the submit: the submit is
    # shorter.
    assert got["batcher.submit"] == pytest.approx(10)
    assert got["batcher.launch"] == pytest.approx(10 + 4 + 40)
    assert sum(got.values()) == pytest.approx(100)


def test_launch_readings_hand_made():
    host = [[_ev("bench.window", 0, 100),
             _ev("batcher.launch", 10, 30), _ev("batcher.pool", 10, 12),
             _ev("engine.execute", 12, 28), _ev("batcher.resolve", 28, 30),
             _ev("batcher.launch", 40, 90), _ev("engine.execute", 41, 89)]]
    device = [[_ev("k", 14, 20), _ev("k", 45, 50)]]
    spans = ps.reduce_program(device, host)["spans"]
    # host time: 20 - 6 = 14 and 50 - 5 = 45
    assert ps.launch_host_ms(spans) == pytest.approx((14 + 45) / 2 * 1e-6)
    slow = ps.slowest_launches(device, host, k=1)
    assert len(slow) == 1
    assert slow[0]["length_s"] == pytest.approx(50 * ns)
    assert slow[0]["busy_s"] == pytest.approx(5 * ns)
    assert slow[0]["by_phase"] == pytest.approx(
        {"engine.execute": 48 * ns, "batcher.launch": 2 * ns})
    assert slow[0]["host"] == {}
    host.append([_ev("Allocate", 42, 60), _ev("Allocate", 50, 70),
                 _ev("Allocate", 95, 99)])
    slow = ps.slowest_launches(device, host, k=1)
    assert slow[0]["host"] == pytest.approx({"Allocate": 28 * ns})


def test_host_activity_by_phase():
    got = ps.host_activity_by_phase(_with_program())
    assert got["Transpose"] == pytest.approx({"executor.stage": 2 * ns,
                                              "executor.dispatch": 1 * ns})
    assert "engine.execute" not in got and "bench.execute" not in got


def test_reduce_cpu_trace_with_engine_spans(tmp_path):
    """The program's spans read from a real trace of ``execute``."""
    rs = np.random.RandomState(0)
    tree = build_octree(rs.uniform(-1, 1, (3000, 3)).astype(np.float32),
                        depth=3)
    engine = CollisionEngine(tree, EngineConfig(mode="wavefront_persistent"))
    o = random_obbs(jax.random.PRNGKey(1), 32)
    plan = plan_queries(OBBs(center=np.asarray(o.center),
                             half=np.asarray(o.half),
                             rot=np.asarray(o.rot)))
    engine.execute(plan)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.execute"):
                engine.execute(plan)
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    _, host = tr.load(path)
    ops = tr.cpu_ops(path)
    assert ops, "the CPU trace holds no XLA operations"
    out = ps.reduce_program([ops], host)
    spans = out["spans"]
    assert len(spans["engine.execute"]) == 3
    assert len(spans["executor.stage"]) == 3
    assert len(spans["executor.dispatch"]) >= 3
    assert len(spans["executor.sync"]) >= 6
    for s, e, busy in spans["executor.sync"]:
        assert 0 <= busy <= e - s
    assert ps.stage_ms(spans) > 0 and ps.sync_ms(spans) >= 0
    base = tr.reduce_events([ops], host, {})
    window_idle = base["window_s"] - base["busy_s"]
    assert sum(v for _, v in out["idle_by_phase"]) == pytest.approx(
        window_idle, rel=1e-6)
    assert ps.OUTSIDE in dict(out["idle_by_phase"])
