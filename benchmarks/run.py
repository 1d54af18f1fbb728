"""Benchmark harness: one function per RoboGPU table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows.  Default sizes are scaled so
the suite finishes on one CPU core; pass --full for paper-scale inputs
(524288-point clouds).  Simulator-cycle/energy claims use the work model in
benchmarks/common.py; wall-clock rows are measured on the JAX engine.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig11,table4
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Support plain-script invocation (python benchmarks/run.py) next to
# module invocation (python -m benchmarks.run): put the repo root and src/
# on sys.path before the package imports below.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ in (None, ""):
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    sys.path.insert(0, _ROOT)

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import (emit, time_call, time_group,
                               work_model_cycles, work_model_energy_pj,
                               write_results)
from repro.core.ballquery import ball_query_pray, ball_query_psphere
from repro.core.fps import (farthest_point_sampling, random_sampling,
                            sampling_spread)
from repro.core.geometry import OBBs
from repro.core.octree import build_octree
from repro.core.quantize import META_FORMATS
from repro.core.wavefront import (CollisionEngine, EngineConfig,
                                  traversal_cache_info)
from repro.data.robotics import (ENVIRONMENTS, make_mpaccel_scenario,
                                 make_scene, scene_trajectories)
from repro.launch.compile_cache import setup_compile_cache

SCALE = {"points": 65536, "trajs": 6, "wps": 30, "depth": 6,
         "mpaccel_scenarios": 4, "mpaccel_points": 16384,
         "edges": 24, "edge_res": 16,
         "serve_clients": 8, "serve_requests": 16, "serve_queries": 12,
         "serve_max_wait_ms": 2.0}
FULL_SCALE = {"points": 524288, "trajs": 25, "wps": 60, "depth": 7,
              "mpaccel_scenarios": 10, "mpaccel_points": 65536,
              "edges": 64, "edge_res": 32,
              "serve_clients": 16, "serve_requests": 32,
              "serve_queries": 12, "serve_max_wait_ms": 2.0}
# CI artifact job: tiny scene, 1 repeat, subset of benches (see --smoke).
SMOKE_SCALE = {"points": 4096, "trajs": 2, "wps": 6, "depth": 4,
               "mpaccel_scenarios": 1, "mpaccel_points": 2048,
               "edges": 8, "edge_res": 16,
               "serve_clients": 4, "serve_requests": 8, "serve_queries": 12,
               "serve_max_wait_ms": 4.0}
SMOKE_BENCHES = ("fig11", "fig15", "table4", "batched", "ragged",
                 "fig_edges", "fig_bigscene", "fig_compress", "fig_serve")

_scene_cache = {}


def get_scene(name, n_points, depth, trajs, wps):
    key = (name, n_points, depth, trajs, wps)
    if key not in _scene_cache:
        sc = make_scene(name, num_points=n_points)
        tree = build_octree(sc.points, depth=depth)
        obbs = scene_trajectories(sc, num_trajectories=trajs, waypoints=wps)
        _scene_cache[key] = (sc, tree, obbs)
    return _scene_cache[key]


# ---------------------------------------------------------------------------
# Fig. 11 — collision detection speedup per environment x design arm
# ---------------------------------------------------------------------------

def fig11_collision_speedup(S):
    rows = {}
    persist_speedups = []
    for env in ENVIRONMENTS:
        _, tree, obbs = get_scene(env, S["points"], S["depth"], S["trajs"],
                                  S["wps"])
        base_cycles = None
        ref = None
        engines = {}
        for mode in ("naive", "rta_like", "staged_noexit", "predicated",
                     "wavefront_host", "wavefront", "wavefront_fused",
                     "wavefront_persistent"):
            eng = CollisionEngine(tree, EngineConfig(mode=mode))
            engines[mode] = eng
            col, c = eng.query(obbs)
            col2, c2 = eng.query(obbs)       # timed second run (post-jit)
            if ref is None:
                ref = np.asarray(col)
            assert (np.asarray(col2) == ref).all(), (env, mode)
            cycles = work_model_cycles(c2, mode)
            if mode == "naive":
                base_cycles = cycles
            speed = base_cycles / cycles
            # escalations: replays of the FIRST (cold) query; the timed
            # second run starts at the memoized clean capacity, so a
            # nonzero repeat count here means the memo regressed.
            emit(f"fig11/{env}/{mode}", c2.wall_time_s * 1e6,
                 f"model_speedup_vs_cuda={speed:.1f};collisions="
                 f"{int(ref.sum())};axis_exec={c2.axis_tests_executed};"
                 f"cold_escalations={c.escalations};"
                 f"escalations={c2.escalations}")
            rows[(env, mode)] = (c2, cycles)
        # headline: RC_CR_CU vs rta_like (paper: 3.1x), vs naive (14.8x)
        full = rows[(env, "wavefront_fused")][1]
        emit(f"fig11/{env}/headline", 0.0,
             f"vs_mochi={rows[(env, 'rta_like')][1]/full:.1f}x;"
             f"vs_cuda={rows[(env, 'naive')][1]/full:.1f}x;"
             f"vs_tta={rows[(env, 'staged_noexit')][1]/full:.1f}x")
        # Wall clock, interleaved best-of-N (single runs are too noisy for
        # the CI regression diff): device while_loop vs host-in-the-loop
        # resize at few repeats (an ~8x gap survives any noise), then the
        # close fused-vs-unfused A/B at many cheap repeats.
        walls_hd = time_group({
            "host": lambda: engines["wavefront_host"].query(obbs),
            "dev": lambda: engines["wavefront"].query(obbs)}, repeats=5)
        walls_df = time_group({
            "dev": lambda: engines["wavefront"].query(obbs),
            "fused": lambda: engines["wavefront_fused"].query(obbs),
            "persist": lambda: engines["wavefront_persistent"].query(obbs)},
            repeats=21)
        host_wall = walls_hd["host"]
        dev_wall = min(walls_hd["dev"], walls_df["dev"])
        fused_wall = walls_df["fused"]
        persist_wall = walls_df["persist"]
        emit(f"fig11/{env}/engine=device_wavefront", dev_wall * 1e6,
             f"wall_speedup_vs_host={host_wall/max(dev_wall, 1e-9):.1f}x")
        emit(f"fig11/{env}/engine=device_fused", fused_wall * 1e6,
             f"wall_speedup_vs_unfused="
             f"{dev_wall/max(fused_wall, 1e-9):.2f}x;"
             f"wall_speedup_vs_host="
             f"{host_wall/max(fused_wall, 1e-9):.1f}x")
        persist_speedups.append(fused_wall / max(persist_wall, 1e-9))
        emit(f"fig11/{env}/engine=device_persistent", persist_wall * 1e6,
             f"wall_speedup_vs_fused={persist_speedups[-1]:.2f}x;"
             f"wall_speedup_vs_host="
             f"{host_wall/max(persist_wall, 1e-9):.1f}x")
    emit("fig11/persistent_vs_fused_geomean", 0.0,
         f"geomean_wall_speedup="
         f"{float(np.exp(np.mean(np.log(persist_speedups)))):.2f}x;"
         f"envs={len(persist_speedups)}")
    # Retrace/replay observability: lru entries and per-key trace counts
    # of the traversal jit cache after the whole fig11 sweep — growth here
    # between runs means escalation replays or engine reconstructions
    # started retracing (BENCH artifacts record the trajectory).
    tc = traversal_cache_info()
    emit("fig11/traversal_cache", 0.0,
         f"entries={tc['entries']};hits={tc['hits']};"
         f"misses={tc['misses']};traces={sum(tc['traces'].values())}")


# ---------------------------------------------------------------------------
# Fig. 12 — unit utilization proxy (work distribution per design)
# ---------------------------------------------------------------------------

def fig12_unit_utilization(S):
    _, tree, obbs = get_scene("cubby", S["points"], S["depth"], S["trajs"],
                              S["wps"])
    for mode in ("staged_noexit", "predicated", "wavefront",
                 "wavefront_fused", "wavefront_persistent"):
        eng = CollisionEngine(tree, EngineConfig(mode=mode))
        _, c = eng.query(obbs)
        total = work_model_cycles(c, mode)
        icnt = c.bytes_moved * 0.05 / max(total, 1)
        box_normal = min(c.axis_tests_executed, c.nodes_traversed * 6)
        edge = max(c.axis_tests_executed - box_normal, 0)
        emit(f"fig12/{mode}", 0.0,
             f"icnt_frac={icnt:.2f};box_normal_tests={box_normal};"
             f"edge_tests={edge};bytes={c.bytes_moved}")


# ---------------------------------------------------------------------------
# Fig. 13 — sensitivity to collision-unit latency (work model)
# ---------------------------------------------------------------------------

def fig13_latency_sensitivity(S):
    from benchmarks import common
    _, tree, obbs = get_scene("cubby", S["points"], S["depth"], S["trajs"],
                              S["wps"])
    counters = {}
    for mode in ("predicated", "wavefront"):
        eng = CollisionEngine(tree, EngineConfig(mode=mode))
        _, counters[mode] = eng.query(obbs)
    base = common.CYCLES_AXIS
    for mult in (0.5, 1.0, 1.5, 2.0):
        common.CYCLES_AXIS = base * mult
        cr = work_model_cycles(counters["wavefront"], "wavefront")
        p = work_model_cycles(counters["predicated"], "predicated")
        emit(f"fig13/lat_{mult}x", 0.0,
             f"cond_return_cycles={cr:.3e};predication_cycles={p:.3e}")
    common.CYCLES_AXIS = base


# ---------------------------------------------------------------------------
# Fig. 14 — MPAccel small scenarios: avg/min/max speedup vs naive
# ---------------------------------------------------------------------------

def fig14_mpaccel(S):
    speeds = []
    for i in range(S["mpaccel_scenarios"]):
        sc = make_mpaccel_scenario(i, num_points=S["mpaccel_points"])
        tree = build_octree(sc.points, depth=5)
        obbs = scene_trajectories(sc, num_trajectories=4, waypoints=25)
        cyc = {}
        for mode in ("naive", "wavefront_fused"):
            eng = CollisionEngine(tree, EngineConfig(mode=mode))
            _, c = eng.query(obbs)
            cyc[mode] = work_model_cycles(c, mode)
        speeds.append(cyc["naive"] / cyc["wavefront_fused"])
    emit("fig14/mpaccel", 0.0,
         f"avg={np.mean(speeds):.1f}x;min={np.min(speeds):.1f}x;"
         f"max={np.max(speeds):.1f}x;"
         f"note=paper_sees_smaller_gains_on_small_scenes")


# ---------------------------------------------------------------------------
# Fig. 15 — latency distribution per exit condition (+ sphere ablation)
# ---------------------------------------------------------------------------

def fig15_exit_distribution(S):
    _, tree, obbs = get_scene("dresser", S["points"], S["depth"],
                              S["trajs"], S["wps"])
    for spheres in (False, True):
        eng = CollisionEngine(tree, EngineConfig(mode="wavefront",
                                                 use_spheres=spheres))
        _, c = eng.query(obbs)
        h = c.exit_histogram
        early = c.early_exit_fraction()
        emit(f"fig15/spheres_{spheres}", 0.0,
             f"bsphere={h[0]};isphere={h[1]};"
             f"box_normal={int(h[2:8].sum())};edge={int(h[8:17].sum())};"
             f"full={h[17]};early_exit_frac={early:.2f};"
             f"sphere_tests={c.sphere_tests}")


# ---------------------------------------------------------------------------
# Fig. 16 — energy model comparison
# ---------------------------------------------------------------------------

def fig16_energy(S):
    _, tree, obbs = get_scene("merged_cubby", S["points"], S["depth"],
                              S["trajs"], S["wps"])
    pj = {}
    for mode in ("naive", "rta_like", "wavefront_fused"):
        eng = CollisionEngine(tree, EngineConfig(mode=mode))
        _, c = eng.query(obbs)
        pj[mode] = work_model_energy_pj(c)
    emit("fig16/energy", 0.0,
         f"vs_cuda_savings={1-pj['wavefront_fused']/pj['naive']:.2f};"
         f"vs_mochi_savings={1-pj['wavefront_fused']/pj['rta_like']:.2f}")


# ---------------------------------------------------------------------------
# Table IV — P-Ray vs P-Sphere ball query
# ---------------------------------------------------------------------------

def table4_pray_psphere(S):
    sc, tree, _ = get_scene("cubby", S["points"], S["depth"], 1, 2)
    rs = np.random.RandomState(0)
    m = 512
    qidx = rs.choice(len(sc.points), m, replace=False)
    queries = jnp.asarray(sc.points[qidx])
    radius, k = 0.05, 32

    t = time.perf_counter()
    ps_idx, ps_cnt, c_ps = ball_query_psphere(tree, queries, radius, k)
    t_ps = time.perf_counter() - t
    t = time.perf_counter()
    pr_idx, pr_cnt, c_pr = ball_query_pray(jnp.asarray(sc.points), queries,
                                           radius, k, depth=4)
    t_pr = time.perf_counter() - t
    assert (np.asarray(ps_cnt) == np.asarray(pr_cnt)).all()
    emit("table4/p_ray", t_pr * 1e6,
         f"rays={len(sc.points)};spheres={m};tree_depth=4;"
         f"nodes={c_pr.nodes_traversed};"
         f"nodes_per_ray={c_pr.nodes_traversed/len(sc.points):.1f}")
    emit("table4/p_sphere", t_ps * 1e6,
         f"rays={m};spheres={len(sc.points)};tree_depth={tree.depth};"
         f"nodes={c_ps.nodes_traversed};"
         f"nodes_per_ray={c_ps.nodes_traversed/m:.1f};"
         f"speedup_vs_pray={t_pr/t_ps:.1f}x")
    # early-exit node saving (paper: 6x fewer nodes)
    _, _, c_ne = ball_query_psphere(tree, queries, radius, k,
                                    early_exit=False)
    emit("table4/early_exit", 0.0,
         f"nodes_with_ee={c_ps.nodes_traversed};"
         f"nodes_without={c_ne.nodes_traversed};"
         f"ratio={c_ne.nodes_traversed/max(c_ps.nodes_traversed,1):.1f}x")


# ---------------------------------------------------------------------------
# Fig. 17 — ball query radius sweep
# ---------------------------------------------------------------------------

def fig17_radius_sweep(S):
    sc, tree, _ = get_scene("cubby", S["points"], S["depth"], 1, 2)
    rs = np.random.RandomState(1)
    queries = jnp.asarray(
        sc.points[rs.choice(len(sc.points), 256, replace=False)])
    base = None
    for r in (0.05, 0.1, 0.2, 0.4):
        t = time.perf_counter()
        _, _, c = ball_query_psphere(tree, queries, r, 32)
        dt = time.perf_counter() - t
        if base is None:
            base = dt
        emit(f"fig17/psphere_r{r}", dt * 1e6,
             f"rel={dt/base:.2f};nodes={c.nodes_traversed}")


# ---------------------------------------------------------------------------
# Fig. 9 — sampling strategy: FPS vs random in the PointNet++ front end
# ---------------------------------------------------------------------------

def fig9_sampling(S):
    from repro.models.pointnet import init_pointnet, pointnet_encode
    rs = np.random.RandomState(0)
    cloud = jnp.asarray(rs.uniform(-1, 1, (2, 2048, 3)).astype(np.float32))
    params = init_pointnet(jax.random.PRNGKey(0))
    enc_fps = jax.jit(lambda p, c: pointnet_encode(p, c, "fps"))
    enc_rnd = jax.jit(lambda p, c, k: pointnet_encode(p, c, "random", k))
    key = jax.random.PRNGKey(1)
    t_fps = time_call(lambda: enc_fps(params, cloud).block_until_ready())
    t_rnd = time_call(
        lambda: enc_rnd(params, cloud, key).block_until_ready())
    pts = cloud[0]
    s_fps = float(sampling_spread(pts, farthest_point_sampling(pts, 256)))
    s_rnd = float(np.mean([float(sampling_spread(
        pts, random_sampling(jax.random.PRNGKey(s), 2048, 256)))
        for s in range(4)]))
    emit("fig9/fps", t_fps * 1e6, f"spread={s_fps:.4f}")
    emit("fig9/random", t_rnd * 1e6,
         f"spread={s_rnd:.4f};latency_saving={1-t_rnd/t_fps:.2f};"
         f"note=collision_gate_catches_quality_loss")


# ---------------------------------------------------------------------------
# Fig. 18 — full pipeline latency breakdown with collision gate
# ---------------------------------------------------------------------------

def fig18_pipeline(S):
    from repro.core.pipeline import plan_with_collision_gate
    from repro.models.planner import init_planner, rollout
    sc, tree, _ = get_scene("tabletop", S["points"], S["depth"], 1, 2)
    engine = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    params = init_planner(jax.random.PRNGKey(0))
    rs = np.random.RandomState(2)
    cloud = jnp.asarray(
        sc.points[rs.choice(len(sc.points), 2048, replace=False)])
    q0 = jnp.asarray(rs.uniform(-1, 1, 7).astype(np.float32))
    goal = jnp.asarray(rs.uniform(-1, 1, 7).astype(np.float32))
    fns = {"rollout": jax.jit(rollout, static_argnames=("num_steps",
                                                        "sampling"))}
    for sampling in ("fps", "random"):
        plan_with_collision_gate(params, fns, engine, cloud, q0, goal,
                                 num_steps=20, sampling=sampling,
                                 key=jax.random.PRNGKey(3))
        res2 = plan_with_collision_gate(params, fns, engine, cloud, q0,
                                        goal, num_steps=20,
                                        sampling=sampling,
                                        key=jax.random.PRNGKey(3))
        t = res2.timings
        emit(f"fig18/{sampling}", (t["plan_s"] + t["collision_s"]) * 1e6,
             f"plan_us={t['plan_s']*1e6:.0f};"
             f"collision_us={t['collision_s']*1e6:.0f};"
             f"collision_free={res2.collision_free}")


# ---------------------------------------------------------------------------
# Fig. 19 — MCL (DeliBot) with dynamic engine switching
# ---------------------------------------------------------------------------

def fig19_mcl(S):
    from repro.core.mcl import (choose_engine, init_particles,
                                make_corridor_world, mcl_step,
                                ray_cast_dense)
    grid = make_corridor_world(jax.random.PRNGKey(0), size=192)
    angles = jnp.linspace(-np.pi, np.pi, 24, endpoint=False)
    true_pose = jnp.asarray([5.0, 5.0, 0.4])
    obs, _ = ray_cast_dense(grid, jnp.tile(true_pose[None, :2], (24, 1)),
                            true_pose[2] + angles, 6.0)
    iters = 8
    results = {}
    for policy in ("dense", "compacted", "dynamic"):
        st = init_particles(jax.random.PRNGKey(1), grid, 192)
        total, cells_hist = 0.0, 1e9
        for it in range(iters):
            eng = (policy if policy != "dynamic"
                   else choose_engine(cells_hist, threshold=60.0))
            st, stats = mcl_step(jax.random.PRNGKey(10 + it), st, grid, obs,
                                 angles, jnp.zeros(3), eng, sigma=0.5)
            cells_hist = stats["cells_per_ray"]
            if it > 0:                     # skip compile iteration
                total += stats["time_s"]
        results[policy] = total
        emit(f"fig19/{policy}", total / max(iters - 1, 1) * 1e6,
             f"cumulative_s={total:.3f}")
    best_fixed = min(results["dense"], results["compacted"])
    emit("fig19/dynamic_vs_best_fixed", 0.0,
         f"speedup={best_fixed/max(results['dynamic'],1e-9):.2f}x")


# ---------------------------------------------------------------------------
# Batched throughput — whole trajectory batch in ONE compiled device call
# vs the host-loop engine iterating trajectory by trajectory
# ---------------------------------------------------------------------------

def batched_throughput(S):
    _, tree, obbs = get_scene("cubby", S["points"], S["depth"], S["trajs"],
                              S["wps"])
    # (trajs, wps*7) batch: one lane per trajectory, early exit per lane.
    B = S["trajs"]
    M = obbs.n // B
    batch = OBBs(center=obbs.center.reshape(B, M, 3),
                 half=obbs.half.reshape(B, M, 3),
                 rot=obbs.rot.reshape(B, M, 3, 3))
    host = CollisionEngine(tree, EngineConfig(mode="wavefront_host"))
    dev = CollisionEngine(tree, EngineConfig(mode="wavefront"))
    fused = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    persist = CollisionEngine(tree, EngineConfig(mode="wavefront_persistent"))
    col_h, _ = host.query_batched(batch)          # warm + reference
    col_d, cd0 = dev.query_batched(batch)         # compile (cold counters)
    col_f, cf0 = fused.query_batched(batch)
    col_p, cp0 = persist.query_batched(batch)
    assert (col_d == col_h).all(), "batched verdict mismatch"
    assert (col_f == col_h).all(), "batched fused verdict mismatch"
    assert (col_p == col_h).all(), "batched persistent verdict mismatch"
    n = B * M
    walls_hd = time_group({"h": lambda: host.query_batched(batch),
                           "d": lambda: dev.query_batched(batch)},
                          repeats=5)
    walls_df = time_group({"d": lambda: dev.query_batched(batch),
                           "f": lambda: fused.query_batched(batch),
                           "p": lambda: persist.query_batched(batch)},
                          repeats=15)
    t_h = walls_hd["h"]
    t_d = min(walls_hd["d"], walls_df["d"])
    t_f = walls_df["f"]
    t_p = walls_df["p"]
    emit("batched/engine=wavefront_host", t_h * 1e6,
         f"queries={n};qps={n/max(t_h, 1e-9):.0f}")
    emit("batched/engine=device_wavefront", t_d * 1e6,
         f"queries={n};qps={n/max(t_d, 1e-9):.0f};"
         f"speedup_vs_host={t_h/max(t_d, 1e-9):.1f}x;"
         f"collisions={int(col_d.sum())};"
         f"cold_escalations={cd0.escalations}")
    emit("batched/engine=device_fused", t_f * 1e6,
         f"queries={n};qps={n/max(t_f, 1e-9):.0f};"
         f"speedup_vs_host={t_h/max(t_f, 1e-9):.1f}x;"
         f"speedup_vs_unfused={t_d/max(t_f, 1e-9):.2f}x;"
         f"collisions={int(col_f.sum())};"
         f"cold_escalations={cf0.escalations}")
    emit("batched/engine=device_persistent", t_p * 1e6,
         f"queries={n};qps={n/max(t_p, 1e-9):.0f};"
         f"speedup_vs_host={t_h/max(t_p, 1e-9):.1f}x;"
         f"speedup_vs_fused={t_f/max(t_p, 1e-9):.2f}x;"
         f"collisions={int(col_p.sum())};"
         f"cold_escalations={cp0.escalations}")
    tc = traversal_cache_info()
    emit("batched/traversal_cache", 0.0,
         f"entries={tc['entries']};hits={tc['hits']};"
         f"misses={tc['misses']};traces={sum(tc['traces'].values())}")


# ---------------------------------------------------------------------------
# Ragged multi-scene frontier — mixed-size scene batch in ONE compiled call
# vs the padded-vmap path that pays the widest scene for every lane
# ---------------------------------------------------------------------------

def ragged_scenes(S):
    from repro.core.octree import build_octree as _build
    from repro.core.wavefront import query_batched_scenes
    rs = np.random.RandomState(0)
    M = max(S["trajs"] * 4, 8)
    depth = max(S["depth"] - 2, 3)

    from repro.core.geometry import random_obbs

    def scene_set(sizes):
        trees, sets = [], []
        for i, n_pts in enumerate(sizes):
            pts = rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32)
            trees.append(_build(pts, depth=depth))
            sets.append(random_obbs(jax.random.PRNGKey(i), M))
        stack = OBBs(center=jnp.stack([o.center for o in sets]),
                     half=jnp.stack([o.half for o in sets]),
                     rot=jnp.stack([o.rot for o in sets]))
        return trees, stack

    small = S["points"] // 16
    trees_s, stack_s = scene_set([small] * 3)             # small-only batch
    trees_m, stack_m = scene_set([small] * 3 + [S["points"]])   # + one big

    # The persistent arms force the Pallas kernel (interpret off-TPU): the
    # ragged mixed-size batch streams per-scene sub-extent windows, and
    # ragged_streamed additionally pins the streamed layout so the format
    # chooser picks a compressed row format.  Both must stay on the kernel
    # arm (ref_arm_fallbacks == 0) — no silent jnp-ref downgrade.
    arms = {
        "padded_wavefront": EngineConfig(mode="wavefront"),
        "ragged_persistent": EngineConfig(mode="wavefront_persistent",
                                          use_pallas_traverse=True),
        "ragged_streamed": EngineConfig(mode="wavefront_persistent",
                                        use_pallas_traverse=True,
                                        stream_meta=True),
    }
    walls, verdicts, counters = {}, {}, {}
    for name, cfg in arms.items():
        for tag, (trees, stack) in (("small", (trees_s, stack_s)),
                                    ("mixed", (trees_m, stack_m))):
            col, c = query_batched_scenes(trees, stack, cfg)  # warm/compile
            verdicts[(name, tag)] = np.asarray(col)
            counters[(name, tag)] = c
            walls[(name, tag)] = time_group(
                {"q": lambda t=trees, st=stack, c=cfg:
                 query_batched_scenes(t, st, c)}, repeats=7)["q"]
    for name in arms:
        for tag in ("small", "mixed"):
            assert (verdicts[(name, tag)]
                    == verdicts[("padded_wavefront", tag)]).all(), (name, tag)
            if name != "padded_wavefront":
                assert counters[(name, tag)].ref_arm_fallbacks == 0, \
                    f"ragged/{name}/{tag} fell back to the jnp ref arm"
        t_small, t_mixed = walls[(name, "small")], walls[(name, "mixed")]
        c = counters[(name, "mixed")]
        # padding evidence: how much does ONE big scene inflate the batch?
        emit(f"ragged/{name}", t_mixed * 1e6,
             f"small_batch_us={t_small*1e6:.0f};"
             f"big_scene_cost={t_mixed/max(t_small, 1e-9):.2f}x;"
             f"nodes={c.nodes_traversed};"
             f"meta_rows_streamed={c.meta_rows_streamed};"
             f"meta_bytes_streamed={c.meta_bytes_streamed};"
             f"ref_arm_fallbacks={c.ref_arm_fallbacks}")
    assert counters[("ragged_streamed", "mixed")].meta_rows_streamed > 0, \
        "ragged_streamed must stream metadata windows"
    t_pad, t_rag = (walls[("padded_wavefront", "mixed")],
                    walls[("ragged_persistent", "mixed")])
    pad_infl = (walls[("padded_wavefront", "mixed")]
                / max(walls[("padded_wavefront", "small")], 1e-9))
    rag_infl = (walls[("ragged_persistent", "mixed")]
                / max(walls[("ragged_persistent", "small")], 1e-9))
    emit("ragged/headline", 0.0,
         f"ragged_vs_padded={t_pad/max(t_rag, 1e-9):.2f}x;"
         f"pad_inflation={pad_infl:.2f}x;"
         f"ragged_inflation={rag_infl:.2f}x")


# ---------------------------------------------------------------------------
# fig_edges — PRM-style batch edge validation: swept-edge (CCD) first-hit
# bisection vs dense waypoint sampling at equal resolution
# ---------------------------------------------------------------------------

def fig_edges(S):
    from repro.core.pipeline import check_edges, check_trajectories
    from repro.core.sweep import edge_waypoints
    from repro.data.robotics import PANDA_JOINT_HI, PANDA_JOINT_LO
    sc, tree, _ = get_scene("cubby", S["points"], S["depth"], S["trajs"],
                            S["wps"])
    rs = np.random.RandomState(0)
    E, R = S["edges"], S["edge_res"]
    jlo, jhi = PANDA_JOINT_LO, PANDA_JOINT_HI
    # PRM edges: short joint-space hops between neighboring samples.
    qf = rs.uniform(jlo, jhi, (E, 7)).astype(np.float32)
    qt = np.clip(qf + rs.uniform(-0.35, 0.35, (E, 7)).astype(np.float32),
                 jlo, jhi)
    base = sc.robot_base
    # The CCD figure runs the persistent megakernel arm: owner-group tiling
    # puts each segment's links (and each edge's racing sub-intervals) in
    # one tile, and the in-kernel payload min-fold retires sibling lanes
    # the moment a group's verdict lands.  use_pallas_traverse=True forces
    # the Pallas kernel even off-TPU (interpret mode) — this figure must
    # never silently downgrade to the jnp ref arm (ref_arm_fallbacks gate).
    engine = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", use_pallas_traverse=True))
    # No-early-exit baseline (fig11's staged_noexit arm, the paper's
    # TTA-style machine): same bisection rounds, but every lane traverses
    # to frontier exhaustion — no in-traversal exit of any kind.
    noexit = CollisionEngine(tree, EngineConfig(mode="staged_noexit"))
    wps = jnp.asarray(edge_waypoints(qf, qt, R))

    res = check_edges(engine, qf, qt, resolution=R, base_pos=base)   # warm
    flags, cd = check_trajectories(engine, wps, base_pos=base)       # warm
    res_nx = check_edges(noexit, qf, qt, resolution=R, base_pos=base)
    # Owner-only ablation: same kernel engine, but owner groups / payload
    # minima reduce on the host AFTER boolean traversals (per-query exits
    # stay) — isolates the in-kernel owner-group early exit alone.
    res_ne = check_edges(engine, qf, qt, resolution=R, base_pos=base,
                         in_traversal_exit=False)
    dense = np.asarray(flags).any(axis=1)
    assert (~dense | res.collide).all(), "swept must upper-bound dense"
    for ab in (res_nx, res_ne):
        assert (ab.collide == res.collide).all() and \
            (ab.first_hit == res.first_hit).all(), \
            "no-exit ablation changed CCD verdicts"
    cs, cn, cx = res.counters, res_ne.counters, res_nx.counters
    assert cs.ref_arm_fallbacks == 0 and cd.ref_arm_fallbacks == 0, \
        "fig_edges must run the Pallas kernel arm (ref-arm fallback seen)"
    exit_ratio = cx.nodes_traversed / max(cs.nodes_traversed, 1)
    owner_ratio = cn.nodes_traversed / max(cs.nodes_traversed, 1)
    assert exit_ratio >= 1.5, \
        f"in-kernel early exit saved only {exit_ratio:.2f}x nodes " \
        f"({cx.nodes_traversed} no-exit vs {cs.nodes_traversed}), want 1.5x"
    walls = time_group(
        {"dense": lambda: check_trajectories(engine, wps, base_pos=base),
         "swept": lambda: check_edges(engine, qf, qt, resolution=R,
                                      base_pos=base),
         "noexit": lambda: check_edges(noexit, qf, qt, resolution=R,
                                       base_pos=base)},
        repeats=3)
    n_wp = E * (R + 1)
    emit("fig_edges/dense_waypoints", walls["dense"] * 1e6,
         f"edges={E};res={R};waypoints={n_wp};"
         f"axis_exec={cd.axis_tests_executed};nodes={cd.nodes_traversed};"
         f"colliding_edges={int(dense.sum())}")
    hits = res.first_hit[res.collide]
    emit("fig_edges/swept", walls["swept"] * 1e6,
         f"edges={E};res={R};axis_exec={cs.axis_tests_executed};"
         f"nodes={cs.nodes_traversed};"
         f"colliding_edges={int(res.collide.sum())};"
         f"mean_first_hit={float(hits.mean()) if hits.size else -1:.3f};"
         f"ref_arm_fallbacks={cs.ref_arm_fallbacks}")
    emit("fig_edges/owner_tiled", walls["swept"] * 1e6,
         f"edges={E};res={R};arm=persistent_kernel;"
         f"nodes_with_exit={cs.nodes_traversed};"
         f"nodes_no_exit={cx.nodes_traversed};"
         f"in_kernel_exit_node_saving={exit_ratio:.2f}x;"
         f"owner_exit_only_saving={owner_ratio:.2f}x;"
         f"ref_arm_fallbacks={cs.ref_arm_fallbacks}")
    emit("fig_edges/headline", 0.0,
         f"axis_tests_dense_over_swept="
         f"{cd.axis_tests_executed / max(cs.axis_tests_executed, 1):.2f}x;"
         f"nodes_dense_over_swept="
         f"{cd.nodes_traversed / max(cs.nodes_traversed, 1):.2f}x;"
         f"wall_dense_over_swept="
         f"{walls['dense'] / max(walls['swept'], 1e-9):.2f}x;"
         f"nodes_noexit_over_exit={exit_ratio:.2f}x")


# ---------------------------------------------------------------------------
# fig_bigscene — scene-size sweep past the metadata residency cap: the
# persistent megakernel switches to streamed HBM->VMEM metadata windows
# (DESIGN.md §3) instead of falling back to the per-level fused arm, and
# must hold its wall advantage there
# ---------------------------------------------------------------------------

def fig_bigscene(S):
    from repro.core.geometry import random_obbs
    from repro.kernels.persist.ops import meta_stream_bytes, meta_table_bytes
    rs = np.random.RandomState(5)
    depth = min(S["depth"] + 1, 8)
    M = max(S["trajs"] * S["wps"], 32)
    # Two uniform clouds: 1x sits at the residency limit (the budget is
    # set to exactly its table size), 6x points lands >= 4x the limit in
    # occupied nodes at this depth.
    trees = {}
    for tag, n_pts in (("small", S["points"]), ("big", 6 * S["points"])):
        pts = rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32)
        trees[tag] = build_octree(pts, depth=depth,
                                  scene_lo=np.full(3, -1.0, np.float32),
                                  scene_size=2.0)
    table_bytes = {tag: meta_table_bytes(
        depth, max(len(l.codes) for l in t.levels))
        for tag, t in trees.items()}
    budget = table_bytes["small"]
    speedups = []
    for tag, tree in trees.items():
        obbs = random_obbs(jax.random.PRNGKey(11), M)
        fused = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
        # fp32 pin: this figure isolates the LAYOUT switch (PR 5 baseline);
        # fig_compress sweeps the row formats on the same scenes.  The
        # kernel arm is forced (interpret off-TPU): past the residency
        # budget the megakernel streams fixed-size sub-level windows
        # instead of downgrading to the jnp ref arm.
        persist = CollisionEngine(tree, EngineConfig(
            mode="wavefront_persistent", vmem_budget=budget,
            meta_format="fp32", use_pallas_traverse=True))
        col_f, _ = fused.query(obbs)                  # compile + reference
        col_p, cp = persist.query(obbs)
        assert (np.asarray(col_p) == np.asarray(col_f)).all(), tag
        assert cp.ref_arm_fallbacks == 0, \
            f"fig_bigscene/{tag} fell back to the jnp ref arm"
        walls = time_group({"fused": lambda: fused.query(obbs),
                            "persist": lambda: persist.query(obbs)},
                           repeats=7)
        speedups.append(walls["fused"] / max(walls["persist"], 1e-9))
        emit(f"fig_bigscene/{tag}/fused", walls["fused"] * 1e6,
             f"queries={M};depth={depth};"
             f"table_bytes={table_bytes[tag]}")
        n_max = max(len(l.codes) for l in tree.levels)
        emit(f"fig_bigscene/{tag}/persistent", walls["persist"] * 1e6,
             f"queries={M};layout={persist.meta_layout};"
             f"meta_rows_streamed={cp.meta_rows_streamed};"
             f"meta_bytes_streamed={cp.meta_bytes_streamed};"
             f"window_bytes={meta_stream_bytes(n_max)};"
             f"overflow={cp.frontier_overflow};"
             f"ref_arm_fallbacks={cp.ref_arm_fallbacks};"
             f"speedup_vs_fused={speedups[-1]:.2f}x")
    emit("fig_bigscene/headline", 0.0,
         f"geomean_speedup_vs_fused="
         f"{float(np.exp(np.mean(np.log(speedups)))):.2f}x;"
         f"bigscene_over_budget="
         f"{table_bytes['big']/max(budget, 1):.1f}x;"
         f"mode_stays=wavefront_persistent")


# ---------------------------------------------------------------------------
# fig_compress — metadata row-format sweep (DESIGN.md §3/§4): streamed
# traversal on the fig_bigscene over-budget scene at fp32 vs bf16 vs u8
# rows.  Verdicts must be bitwise-identical; u8 must stream >= 3x fewer
# metadata bytes (it streams exactly 4x fewer: the row COUNT is
# format-independent and only the row width changes) at no wall cost.
# CI requires this row family (--require fig_compress).
# ---------------------------------------------------------------------------

def fig_compress(S):
    from repro.core.geometry import random_obbs
    from repro.kernels.persist.ops import (META_FORMAT_BYTES,
                                           meta_stream_bytes,
                                           meta_table_bytes)
    rs = np.random.RandomState(5)
    depth = min(S["depth"] + 1, 8)
    M = max(S["trajs"] * S["wps"], 32)
    # The fig_bigscene over-budget scene: 6x points at depth+1, budget set
    # to the small (1x) cloud's fp32 table so this one always streams.
    small = build_octree(
        rs.uniform(-1, 1, (S["points"], 3)).astype(np.float32), depth=depth,
        scene_lo=np.full(3, -1.0, np.float32), scene_size=2.0)
    tree = build_octree(
        rs.uniform(-1, 1, (6 * S["points"], 3)).astype(np.float32),
        depth=depth, scene_lo=np.full(3, -1.0, np.float32), scene_size=2.0)
    budget = meta_table_bytes(depth, max(len(l.codes) for l in small.levels))
    n_max = max(len(l.codes) for l in tree.levels)
    obbs = random_obbs(jax.random.PRNGKey(11), M)
    ref_v, _ = CollisionEngine(
        tree, EngineConfig(mode="wavefront_fused")).query(obbs)
    stats, walls_by_fmt = {}, {}
    for fmt in META_FORMATS:
        eng = CollisionEngine(tree, EngineConfig(
            mode="wavefront_persistent", vmem_budget=budget,
            stream_meta=True, meta_format=fmt))
        assert eng.meta_layout == "streamed", fmt
        v, c = eng.query(obbs)                        # compile + reference
        assert (np.asarray(v) == np.asarray(ref_v)).all(), fmt
        assert c.meta_bytes_streamed == \
            c.meta_rows_streamed * META_FORMAT_BYTES[fmt], fmt
        stats[fmt] = c
        walls_by_fmt[fmt] = eng
    walls = time_group(
        {fmt: (lambda e=eng: e.query(obbs))
         for fmt, eng in walls_by_fmt.items()}, repeats=7)
    for fmt in META_FORMATS:
        c = stats[fmt]
        emit(f"fig_compress/{fmt}", walls[fmt] * 1e6,
             f"queries={M};depth={depth};layout=streamed;"
             f"meta_rows_streamed={c.meta_rows_streamed};"
             f"meta_bytes_streamed={c.meta_bytes_streamed};"
             f"window_bytes={meta_stream_bytes(n_max, fmt)};"
             f"nodes={c.nodes_traversed};"
             f"bytes_vs_fp32="
             f"{stats['fp32'].meta_bytes_streamed / max(c.meta_bytes_streamed, 1):.2f}x")
    # Scene capacity per VMEM byte under the RESIDENT layout scales
    # inversely with row width: rows-per-budget at each format.
    cap = {fmt: budget // ((depth + 1) * META_FORMAT_BYTES[fmt])
           for fmt in META_FORMATS}
    emit("fig_compress/headline", 0.0,
         f"u8_bytes_reduction="
         f"{stats['fp32'].meta_bytes_streamed / max(stats['u8'].meta_bytes_streamed, 1):.2f}x;"
         f"rows_equal={int(stats['fp32'].meta_rows_streamed == stats['u8'].meta_rows_streamed)};"
         f"verdicts=bitwise_identical;"
         f"scene_per_vmem_byte_u8_vs_fp32={cap['u8'] / max(cap['fp32'], 1):.2f}x;"
         f"wall_u8_over_fp32={walls['u8'] / max(walls['fp32'], 1e-9):.2f}x")
    assert stats["fp32"].meta_bytes_streamed \
        >= 3 * stats["u8"].meta_bytes_streamed, "u8 must cut bytes >= 3x"


# ---------------------------------------------------------------------------
# fig_serve — collision service SLOs (DESIGN.md §6): N closed-loop clients
# submit small query sets through the continuous batcher over one engine;
# reports client-observed p50/p99 latency, queries/sec, and batching
# effectiveness.  CI requires this row family (--require fig_serve).
# ---------------------------------------------------------------------------

def fig_serve(S):
    from repro.launch.serve import run_service
    _, tree, _ = get_scene(ENVIRONMENTS[0], S["points"], S["depth"],
                           S["trajs"], S["wps"])
    rep = run_service(tree, clients=S["serve_clients"],
                      requests=S["serve_requests"],
                      queries_per_request=S["serve_queries"],
                      max_wait_ms=S["serve_max_wait_ms"])
    emit("fig_serve/latency", rep["p50_ms"] * 1e3,
         f"p50_ms={rep['p50_ms']:.2f};p99_ms={rep['p99_ms']:.2f};"
         f"clients={rep['clients']};requests={rep['requests']};"
         f"queries_per_request={S['serve_queries']};"
         f"max_wait_ms={S['serve_max_wait_ms']}")
    emit("fig_serve/throughput", 0.0,
         f"qps={rep['qps']:.0f};rps={rep['rps']:.0f};"
         f"queries={rep['queries']};wall_s={rep['wall_s']:.2f}")
    emit("fig_serve/batching", 0.0,
         f"launches={rep['launches']};"
         f"req_per_launch={rep['mean_requests_per_launch']:.1f};"
         f"live_q_per_launch={rep['mean_live_queries_per_launch']:.0f};"
         f"pad_fraction={rep['pad_fraction']:.2f}")
    # Reliability counters (DESIGN.md §7): all zero on this healthy run —
    # the row existing is the point (check_regression would flag a chaos-
    # mode counter leaking into the clean-path service).
    emit("fig_serve/reliability", 0.0,
         f"submitted={rep['submitted']};completed={rep['requests']};"
         f"failed={rep['failed']};rejected={rep['rejected']};"
         f"retried={rep['retried']};"
         f"deadline_missed={rep['deadline_missed']};"
         f"launch_splits={rep['launch_splits']};"
         f"worker_restarts={rep['worker_restarts']};"
         f"reshards={rep['reshards']};"
         f"shards_lost={rep['shards_lost']};"
         f"shard_rescales={rep['shard_rescales']};"
         f"degraded_launches={rep['degraded_launches']}")


# ---------------------------------------------------------------------------
# Roofline table (reads the dry-run artifacts; §Roofline source of truth)
# ---------------------------------------------------------------------------

def roofline_table(S):
    d = os.path.join(os.path.dirname(__file__), "results", "dryrun")
    if not os.path.isdir(d):
        emit("roofline/missing", 0.0, "run repro.lm.dryrun first")
        return
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(d, fn)) as f:
            r = json.load(f)
        if r.get("status") == "skipped":
            emit(f"roofline/{r['cell']}", 0.0, f"skipped:{r['reason'][:60]}")
            continue
        if r.get("status") != "ok":
            emit(f"roofline/{r['cell']}", 0.0, "ERROR")
            continue
        emit(f"roofline/{r['cell']}", r["compile_s"] * 1e6,
             f"compute_s={r['compute_s']:.3f};memory_s={r['memory_s']:.3f};"
             f"collective_s={r['collective_s']:.3f};"
             f"dominant={r['dominant']};"
             f"useful_ratio={r['useful_flops_ratio']:.2f};"
             f"mem_gb={r['peak_mem_per_chip']/1e9:.1f}")


BENCHES = {
    "fig9": fig9_sampling,
    "fig11": fig11_collision_speedup,
    "fig12": fig12_unit_utilization,
    "fig13": fig13_latency_sensitivity,
    "fig14": fig14_mpaccel,
    "fig15": fig15_exit_distribution,
    "fig16": fig16_energy,
    "table4": table4_pray_psphere,
    "fig17": fig17_radius_sweep,
    "fig18": fig18_pipeline,
    "fig19": fig19_mcl,
    "batched": batched_throughput,
    "ragged": ragged_scenes,
    "fig_edges": fig_edges,
    "fig_bigscene": fig_bigscene,
    "fig_compress": fig_compress,
    "fig_serve": fig_serve,
    "roofline": roofline_table,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale inputs (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny scene, 1 repeat, writes artifacts")
    ap.add_argument("--out", default=None,
                    help="directory for results.csv/results.json artifacts")
    args = ap.parse_args()
    setup_compile_cache()
    if args.smoke:
        S = SMOKE_SCALE
        names = args.only.split(",") if args.only else list(SMOKE_BENCHES)
        if args.out is None:
            args.out = os.path.join(os.path.dirname(__file__), "results",
                                    "smoke")
    else:
        S = FULL_SCALE if args.full else SCALE
        names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    errors = 0
    for name in names:
        t0 = time.time()
        try:
            BENCHES[name](S)
        except Exception as e:  # keep the suite going
            import traceback
            traceback.print_exc()
            emit(f"{name}/ERROR", 0.0, repr(e)[:120])
            errors += 1
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if args.out:
        write_results(args.out)
        print(f"# artifacts written to {args.out}", flush=True)
    if args.smoke and errors:
        # CI gate: a smoke run with crashed benches must fail the job, not
        # just leave ERROR rows in the artifact.
        raise SystemExit(f"{errors} benchmark(s) failed")


if __name__ == "__main__":
    main()
