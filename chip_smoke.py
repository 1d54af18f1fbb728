#!/usr/bin/env python3
"""Smoke test of the collision engine's main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on four chips

One chip: the paper's Table III workload (the ``cubby`` scene at 524,288
points, depth 7; 25 trajectories x 60 waypoints x 7 links = 10,500 link
OBBs) through ``repro.engine`` on four device arms — ``wavefront_persistent``
with the layout chooser's own pick, ``wavefront_persistent`` pinned to
streamed u8 rows, ``wavefront_fused`` and ``wavefront``.  The arms must
agree bitwise on verdicts and work counters, report zero frontier overflow
and zero ref-arm fallbacks, and match the all-pairs ``naive`` oracle on
the first 1,050 OBBs.  The persistent arm must lower to a Pallas kernel
(``tpu_custom_call``).  Then 8 clients x 4 requests x 12 OBBs go through
``launch/serve.run_service`` on the persistent engine, with no failures.

Four chips (``--chips 4``): only the sharded path and its comparison —
``wavefront_fused`` and ``wavefront_persistent`` at ``shards=4`` against
``shards=1`` on the same plan in this process (bitwise-equal verdicts and
counters), then ``run_service`` on the four-shard fused engine.

Every phase is fatal.  Timings printed are one warm sample each, not a
benchmark.  The last line of standard output is the JSON result
``{"ok": true, "device": {...}}``; without a TPU the script exits non-zero
before printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

PAPER_POINTS = 524288
PAPER_DEPTH = 7
ORACLE_OBBS = 1050
WORK_FIELDS = ("nodes_traversed", "nodes_per_level", "leaf_tests",
               "axis_tests_executed", "axis_tests_decoded", "sphere_tests",
               "frontier_overflow")


def log(msg: str) -> None:
    print(msg, flush=True)


def work(c) -> dict:
    return {f: getattr(c, f) for f in WORK_FIELDS} | {
        "exit_histogram": [int(x) for x in c.exit_histogram]}


def paper_workload(points: int = PAPER_POINTS, depth: int = PAPER_DEPTH,
                   trajectories: int = 25, waypoints: int = 60):
    """The cubby scene, its octree, and the Table III link-OBB batch."""
    from repro.core.octree import build_octree
    from repro.data.robotics import make_scene, scene_trajectories

    t0 = time.perf_counter()
    scene = make_scene("cubby", seed=0, num_points=points)
    tree = build_octree(scene.points, depth=depth)
    obbs = scene_trajectories(scene, trajectories, waypoints, seed=0)
    log(f"scene cubby: {points} points, depth {depth}, "
        f"{tree.num_leaves} leaves, level widths "
        f"{[len(l.codes) for l in tree.levels]}, {obbs.n} OBBs "
        f"(set-up {time.perf_counter() - t0:.1f} s)")
    return tree, obbs


def run_arm(name: str, engine, obbs):
    """Cold call (compiles + escalation) then one warm sample."""
    import numpy as np

    t0 = time.perf_counter()
    col, c = engine.query(obbs)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    col2, c2 = engine.query(obbs)
    warm = time.perf_counter() - t0
    assert (np.asarray(col) == np.asarray(col2)).all(), \
        f"{name}: warm verdicts differ from the cold call"
    assert work(c) == work(c2), f"{name}: warm counters differ"
    assert c2.frontier_overflow == 0, \
        f"{name}: frontier_overflow={c2.frontier_overflow}"
    assert c2.ref_arm_fallbacks == 0, \
        f"{name}: ref_arm_fallbacks={c2.ref_arm_fallbacks}"
    log(f"arm {name}: cold (compile + escalation) {cold:.2f} s, "
        f"warm {warm * 1e3:.2f} ms (one sample), hits "
        f"{int(np.asarray(col2).sum())}/{len(col2)}, nodes "
        f"{c2.nodes_traversed}, per level {c2.nodes_per_level}, axis tests "
        f"{c2.axis_tests_executed}, escalations {c.escalations}, meta rows "
        f"streamed {c2.meta_rows_streamed}")
    return np.asarray(col2), c2


def check_arms(tree, obbs, engine_kw=None) -> object:
    """The four device arms agree bitwise; returns the persistent engine."""
    import numpy as np
    from repro.engine import CollisionEngine, EngineConfig

    kw = dict(engine_kw or {})
    arms = {
        "wavefront_persistent": EngineConfig(mode="wavefront_persistent",
                                             **kw),
        "wavefront_persistent/streamed_u8": EngineConfig(
            mode="wavefront_persistent", stream_meta=True, meta_format="u8",
            **kw),
        "wavefront_fused": EngineConfig(mode="wavefront_fused", **kw),
        "wavefront": EngineConfig(mode="wavefront", **kw),
    }
    engines, results = {}, {}
    for name, cfg in arms.items():
        engines[name] = CollisionEngine(tree, cfg)
        if cfg.mode == "wavefront_persistent":
            log(f"arm {name}: metadata layout "
                f"{engines[name].meta_layout}, format "
                f"{engines[name].meta_format}")
        results[name] = run_arm(name, engines[name], obbs)
    ref_col, ref_c = results["wavefront_persistent"]
    for name, (col, c) in results.items():
        assert (col == ref_col).all(), \
            f"{name} verdicts differ from wavefront_persistent in " \
            f"{int((col != ref_col).sum())} of {len(col)} OBBs"
        assert work(c) == work(ref_c), \
            f"{name} work counters differ from wavefront_persistent"
    log(f"arms agree bitwise on {len(ref_col)} verdicts and work counters")
    return engines["wavefront_persistent"], ref_col


def check_oracle(tree, obbs, verdicts, n: int = ORACLE_OBBS) -> None:
    """Persistent verdicts == the all-pairs naive oracle on ``n`` OBBs."""
    import numpy as np
    from repro.core.geometry import OBBs
    from repro.engine import CollisionEngine, EngineConfig

    sub = OBBs(center=obbs.center[:n], half=obbs.half[:n],
               rot=obbs.rot[:n])
    t0 = time.perf_counter()
    naive, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(sub)
    naive = np.asarray(naive)
    assert (naive == verdicts[:n]).all(), \
        f"naive oracle disagrees on {int((naive != verdicts[:n]).sum())} " \
        f"of {n} OBBs"
    log(f"naive oracle agrees on the first {n} OBBs "
        f"({int(naive.sum())} hits, {time.perf_counter() - t0:.1f} s)")


def check_kernel_lowered(engine, obbs) -> None:
    """The persistent engine's own cached traversal — the program its
    queries ran, at the frontier capacity they settled on — lowers to a
    Pallas kernel on this backend."""
    from repro.engine import plan_queries

    plan = plan_queries(obbs)
    cap = engine._cap_memo[("single", plan.num_queries, False, None,
                            engine._scene_sig)]
    fn = engine._run(cap, streamed=engine.meta_layout == "streamed",
                     meta_format=engine.meta_format, use_pallas_traverse=True)
    text = fn.lower(plan.obb_c, plan.obb_h, plan.obb_r, engine.device_tree,
                    None, None, None).as_text()
    assert "tpu_custom_call" in text, \
        "the persistent arm did not lower to a TPU kernel"
    log(f"persistent arm (capacity {cap}) lowers to tpu_custom_call")


def check_serve(tree, engine) -> None:
    from repro.launch.serve import run_service

    rep = run_service(tree, clients=8, requests=4, queries_per_request=12,
                      engine=engine)
    log("serve report: " + json.dumps(
        {k: v for k, v in rep.items() if isinstance(v, (int, float, str))},
        sort_keys=True))
    assert rep["failed"] == 0, f"{rep['failed']} requests failed"
    assert rep["requests"] == rep["submitted"], \
        f"{rep['requests']} of {rep['submitted']} requests completed"


def check_sharded(tree, obbs, shards: int) -> None:
    """shards=N == shards=1 bitwise for the fused and persistent arms, then
    the service on the N-shard fused engine."""
    import numpy as np
    from repro.engine import CollisionEngine, EngineConfig, plan_queries

    plan = plan_queries(obbs)
    sharded_fused = None
    for mode in ("wavefront_fused", "wavefront_persistent"):
        out = {}
        for n in (1, shards):
            eng = CollisionEngine(tree, EngineConfig(mode=mode, shards=n))
            t0 = time.perf_counter()
            col, c = eng.execute(plan)
            cold = time.perf_counter() - t0
            t0 = time.perf_counter()
            col, c = eng.execute(plan)
            warm = time.perf_counter() - t0
            assert c.frontier_overflow == 0, (mode, n, c.frontier_overflow)
            out[n] = (np.asarray(col), c)
            log(f"{mode} shards={n}: cold {cold:.2f} s, warm "
                f"{warm * 1e3:.2f} ms (one sample), hits "
                f"{int(np.asarray(col).sum())}, nodes {c.nodes_traversed}, "
                f"pad queries {c.pad_queries}")
            if mode == "wavefront_fused" and n == shards:
                sharded_fused = eng
        (col1, c1), (coln, cn) = out[1], out[shards]
        assert (col1 == coln).all(), \
            f"{mode}: shards={shards} verdicts differ from shards=1"
        assert work(c1) == work(cn), \
            f"{mode}: shards={shards} counters differ from shards=1"
        log(f"{mode}: shards={shards} == shards=1 bitwise")
    check_serve(tree, sharded_fused)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded path and its one-shard comparison")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    log(f"compile cache: {setup_compile_cache()}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX sees {dev.platform} devices", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU devices, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}")

    tree, obbs = paper_workload()
    if args.chips == 1:
        engine, verdicts = check_arms(tree, obbs)
        check_oracle(tree, obbs, verdicts)
        check_kernel_lowered(engine, obbs)
        check_serve(tree, engine)
    else:
        check_sharded(tree, obbs, args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
