"""Profiler spans of the engine and the batcher, read back from a trace
recorded on the CPU (the Pallas megakernel in interpret mode).

One module fixture records one profiler session (a process holds at most
one): a persistent-mode ``execute`` and three ``RequestBatcher`` requests,
each also run once with the profiler off for the bitwise comparison.
"""
import dataclasses
import glob
import os
import sys
import types
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.counters import Counters
from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import build_octree
from repro.engine import (CollisionEngine, EngineConfig, RequestBatcher,
                          plan_queries)
from repro.engine.executor import _traversal_fn

#: Every span name the engine and the batcher record.
SPANS = ("engine.execute", "executor.stage", "executor.dispatch",
         "executor.sync", "batcher.submit", "batcher.coalesce",
         "batcher.launch", "batcher.pool", "batcher.resolve")


def _numpy_obbs(key, n) -> OBBs:
    o = random_obbs(jax.random.PRNGKey(key), n)
    return OBBs(center=np.asarray(o.center), half=np.asarray(o.half),
                rot=np.asarray(o.rot))


def _serve(engine, requests):
    with RequestBatcher(engine, max_batch=1024, max_wait_ms=50.0) as b:
        tickets = [b.submit(r) for r in requests]
        return [t.result(timeout=300) for t in tickets]


def _spans(log_dir):
    """{name: [(start_ns, end_ns, thread line, {attribute: value})]}."""
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {n: [] for n in SPANS}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        (plane.name, k), dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    rs = np.random.RandomState(2)
    tree = build_octree(rs.uniform(-1, 1, (8000, 3)).astype(np.float32),
                        depth=4)
    engine = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", use_pallas_traverse=True))
    plan = plan_queries(_numpy_obbs(3, 40))
    requests = [_numpy_obbs(10 + i, 8) for i in range(3)]
    engine.execute(plan)                   # compile, settle the capacity
    off = engine.execute(plan)
    off_served = _serve(engine, requests)

    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        on = engine.execute(plan)
        on_served = _serve(engine, requests)
    finally:
        jax.profiler.stop_trace()
    return dict(off=off, on=on, off_served=off_served, on_served=on_served,
                spans=_spans(log_dir), queries=plan.num_queries,
                engine=engine, plan=plan)


def _inside(outer, inner) -> bool:
    return (outer[2] == inner[2] and outer[0] <= inner[0]
            and inner[1] <= outer[1])


def test_execute_spans_nest_inside_engine_execute(recorded):
    """A persistent-mode execute records stage, dispatch and the three
    blocking reads inside its ``engine.execute`` span."""
    spans = recorded["spans"]
    direct = [s for s in spans["engine.execute"]
              if s[3]["queries"] == recorded["queries"]]
    assert len(direct) == 1
    call = direct[0]
    assert call[3]["kind"] == "queries"
    inner = {n: [s for s in spans[n] if _inside(call, s)]
             for n in ("executor.stage", "executor.dispatch",
                       "executor.sync")}
    assert len(inner["executor.stage"]) == 1
    assert inner["executor.stage"][0][3]["queries"] == recorded["queries"]
    assert len(inner["executor.dispatch"]) >= 1
    assert [s[3]["rung"] for s in inner["executor.dispatch"]] == \
        list(range(len(inner["executor.dispatch"])))
    assert all(s[3]["capacity"] > 0 for s in inner["executor.dispatch"])
    assert sorted(s[3]["what"] for s in inner["executor.sync"]) == \
        ["counters", "overflow", "verdict"]
    # Stage precedes every dispatch; the verdict read comes last.
    stage = inner["executor.stage"][0]
    assert all(stage[1] <= d[0] for d in inner["executor.dispatch"])
    last = max(inner["executor.sync"], key=lambda s: s[0])
    assert last[3]["what"] == "verdict"


def test_batcher_spans_join_requests_to_launches(recorded):
    """Three submits record distinct request ids; every launch that carried
    them is a ``batcher.launch`` span whose ``launch`` is the requests'
    ``RequestStats.launch_id``, with the engine's call nested inside."""
    spans = recorded["spans"]
    stats = [s for _, s in recorded["on_served"]]
    submits = [s[3]["request"] for s in spans["batcher.submit"]]
    assert len(submits) == 3 and len(set(submits)) == 3
    assert sorted(s.request_id for s in stats) == sorted(submits)
    launches = {s[3]["launch"]: s for s in spans["batcher.launch"]}
    carried = {}
    for s in stats:
        carried[s.launch_id] = carried.get(s.launch_id, 0) + 1
    assert set(carried) <= set(launches)
    for launch_id, n in carried.items():
        span = launches[launch_id]
        assert span[3]["requests"] == n
        assert span[3]["depth"] == 0
        assert span[3]["live"] == 8 * n
        assert span[3]["pad"] == 64 - 8 * n
        for name in ("batcher.pool", "batcher.resolve", "engine.execute"):
            assert any(_inside(span, s) for s in spans[name]), name
    coalesced = sorted(s[3]["requests"] for s in spans["batcher.coalesce"])
    assert sum(coalesced) == 3


def test_profiler_leaves_verdicts_and_counters_bitwise(recorded):
    """Verdicts and every Counters field but the wall clock are the same
    with the profiler on and off, for the engine and the batcher."""
    def fields(c):
        d = dataclasses.asdict(c)
        d.pop("wall_time_s")
        d["exit_histogram"] = d["exit_histogram"].tolist()
        return d

    (v_off, c_off), (v_on, c_on) = recorded["off"], recorded["on"]
    assert v_off.dtype == v_on.dtype and (v_off == v_on).all()
    assert fields(c_off) == fields(c_on)
    assert c_on.escalations == 0
    for (a, _), (b, _) in zip(recorded["off_served"],
                              recorded["on_served"]):
        assert a.dtype == b.dtype and (a == b).all()


def test_traversal_runs_under_named_scope(recorded):
    """The jitted traversal's operations carry the ``collide_traversal``
    scope, so a device trace names them whatever the kernel arm."""
    engine, plan = recorded["engine"], recorded["plan"]
    fn = _traversal_fn(engine.cfg.mode, "single", 1024, False, True,
                       engine.meta_layout == "streamed", engine.meta_format)
    text = fn.lower(plan.obb_c, plan.obb_h, plan.obb_r,
                    engine.device_tree).as_text(debug_info=True)
    assert "collide_traversal" in text


class _Echo:
    """An engine that answers every query free at once."""

    octree = types.SimpleNamespace(scene_lo=np.zeros(3, np.float32))

    def execute(self, plan, max_depth=None):
        return np.zeros(plan.num_queries, bool), Counters()


def test_request_ids_distinct_under_concurrent_submits():
    """Sixteen client threads submitting at once get distinct, gapless
    request ids, and every request of a launch carries its launch id."""
    obbs = _numpy_obbs(20, 4)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with RequestBatcher(_Echo(), max_wait_ms=0.5) as b:
            def client():
                return [b.submit(obbs).result(timeout=60)[1]
                        for _ in range(25)]
            with ThreadPoolExecutor(16) as pool:
                futures = [pool.submit(client) for _ in range(16)]
                stats = [s for f in futures for s in f.result(timeout=120)]
    finally:
        sys.setswitchinterval(old)
    assert sorted(s.request_id for s in stats) == list(range(400))
    by_launch = {}
    for s in stats:
        by_launch.setdefault(s.launch_id, []).append(s)
    assert min(by_launch) >= 0
    for group in by_launch.values():
        assert all(s.batch_requests == len(group) for s in group)
