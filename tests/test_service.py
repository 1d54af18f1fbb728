"""Collision service layer: sharded execution, continuous batching, SLOs.

Covers DESIGN.md §6: the sharded execute path must be bitwise-identical
to single-device on verdicts AND counters (in-process with shards=1 on
any backend; on 8 virtual CPU devices — including an uneven shard count
that forces padding — via the subprocess helper), the batcher must route
K coalesced requests back to K callers independent of arrival order, and
the serve harness must report the SLO quantities end to end.
"""
import threading

import numpy as np
import jax
import pytest

from test_distributed import run_devices

from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import build_octree
from repro.engine.batcher import RequestBatcher, _pad_bucket
from repro.engine.executor import CollisionEngine, EngineConfig
from repro.engine.plan import (plan_edges, plan_queries, plan_scenes,
                               plan_trajectory)


def _tree(seed, n=3000, depth=4):
    rs = np.random.RandomState(seed)
    return build_octree(rs.uniform(-1, 1, (n, 3)).astype(np.float32),
                        depth=depth)


# ---------------------------------------------------------------------------
# Sharded execution
# ---------------------------------------------------------------------------

def _assert_counters_equal(c0, c1, ctx):
    d0, d1 = c0.as_dict(), c1.as_dict()
    for k in d0:
        if k in ("wall_time_s", "pad_queries"):
            continue
        assert np.all(np.asarray(d0[k]) == np.asarray(d1[k])), \
            (ctx, k, d0[k], d1[k])


def test_sharded_persistent_over_vmem_budget_raises_before_compile():
    """Sharded persistent runs pin the resident fp32 table; a scene whose
    table exceeds the VMEM budget is refused with a ValueError naming the
    budget, before anything is traced or compiled."""
    from repro.engine.executor import traversal_cache_info
    tree = _tree(0)
    eng = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", shards=1, use_pallas_traverse=True,
        vmem_budget=1024))
    before = traversal_cache_info()
    with pytest.raises(ValueError, match="VMEM"):
        eng.execute(plan_queries(random_obbs(jax.random.PRNGKey(1), 8)))
    assert traversal_cache_info() == before


@pytest.mark.parametrize("mode", ["wavefront", "wavefront_fused",
                                  "wavefront_persistent"])
def test_sharded_one_shard_matches_single_device(mode):
    """shards=1 routes the shard_map path on any backend; verdicts and
    every counter must be bitwise-identical to the unsharded engine."""
    tree = _tree(0)
    obbs = random_obbs(jax.random.PRNGKey(1), 37)
    plan = plan_queries(obbs)
    cfg = dict(mode=mode, frontier_capacity=4096)
    v0, c0 = CollisionEngine(tree, EngineConfig(**cfg)).execute(plan)
    v1, c1 = CollisionEngine(
        tree, EngineConfig(**cfg, shards=1)).execute(plan)
    assert (v0 == v1).all()
    _assert_counters_equal(c0, c1, mode)
    assert c1.pad_queries == 0


def test_sharded_eight_devices_bitwise_identical():
    """8-way sharding on 8 virtual CPU devices: even (96) and uneven (101,
    forces per-shard padding) pool sizes, verdicts AND counters."""
    out = run_devices("""
    from repro.core.geometry import random_obbs
    from repro.core.octree import build_octree
    from repro.engine.executor import CollisionEngine, EngineConfig
    from repro.engine.plan import plan_queries

    rs = np.random.RandomState(0)
    tree = build_octree(rs.uniform(-1, 1, (2000, 3)).astype(np.float32),
                        depth=3)
    cases = [("wavefront_fused", 96), ("wavefront_fused", 101),
             ("wavefront_persistent", 101)]
    for mode, Q in cases:
        obbs = random_obbs(jax.random.PRNGKey(Q), Q)
        plan = plan_queries(obbs)
        v0, c0 = CollisionEngine(tree, EngineConfig(
            mode=mode, frontier_capacity=4096)).execute(plan)
        v1, c1 = CollisionEngine(tree, EngineConfig(
            mode=mode, frontier_capacity=4096, shards=8)).execute(plan)
        assert (v0 == v1).all(), (mode, Q)
        d0, d1 = c0.as_dict(), c1.as_dict()
        for k in d0:
            # Gather products are per kernel tile: 8 shards cut the pool
            # into other tiles, so only their presence carries over.
            if k in ("wall_time_s", "pad_queries", "meta_gathers"):
                continue
            assert np.all(np.asarray(d0[k]) == np.asarray(d1[k])), \\
                (mode, Q, k, d0[k], d1[k])
        assert (c0.meta_gathers > 0) == (c1.meta_gathers > 0) \\
            == (mode == "wavefront_persistent"), (mode, Q)
        assert c1.pad_queries == (-Q) % 8, (Q, c1.pad_queries)
        print("SHARDED_OK", mode, Q, c0.nodes_traversed)
    """)
    assert out.count("SHARDED_OK") == 3


def test_sharded_config_and_plan_validation():
    with pytest.raises(ValueError):
        EngineConfig(mode="wavefront_host", shards=2)
    with pytest.raises(ValueError):
        EngineConfig(mode="wavefront_fused", shards=0)
    tree = _tree(1, n=800, depth=3)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused",
                                             shards=1))
    obbs = random_obbs(jax.random.PRNGKey(2), 8)
    with pytest.raises(ValueError):           # owner/payload lanes
        eng.execute(plan_edges(obbs, np.zeros(8, np.int32), 1))
    batch = OBBs(center=obbs.center.reshape(2, 4, 3),
                 half=obbs.half.reshape(2, 4, 3),
                 rot=obbs.rot.reshape(2, 4, 3, 3))
    eng2 = CollisionEngine([tree, _tree(2, n=800, depth=3)],
                           EngineConfig(mode="wavefront_fused", shards=1))
    with pytest.raises(ValueError):           # multi-scene pool
        eng2.execute(plan_scenes(batch))


def test_collision_mesh_validation():
    from repro.parallel.sharding import make_collision_mesh
    with pytest.raises(ValueError):
        make_collision_mesh(0)
    with pytest.raises(ValueError):
        make_collision_mesh(len(jax.devices()) + 1)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

def test_batcher_routes_k_requests_order_independent():
    """K concurrent requests of mixed sizes coalesce into fewer launches
    and every caller gets exactly its own verdicts back."""
    tree = _tree(3)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    K = 10
    reqs = [random_obbs(jax.random.PRNGKey(i), 3 + (7 * i) % 11)
            for i in range(K)]
    refs = [eng.execute(plan_queries(o))[0] for o in reqs]

    with RequestBatcher(eng, max_batch=4096, max_wait_ms=250.0) as b:
        tickets = [None] * K

        def submit(i):
            tickets[i] = b.submit(reqs[i])

        # submit from K threads in no particular order
        threads = [threading.Thread(target=submit, args=(i,))
                   for i in reversed(range(K))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        results = [tickets[i].result(timeout=120) for i in range(K)]
        launches = b.num_launches
    for i, (verdict, stats) in enumerate(results):
        assert verdict.shape == (reqs[i].n,)
        assert (verdict == refs[i]).all(), i
        assert stats.total_s >= stats.exec_s >= 0
        assert stats.wait_s >= 0
        assert 1 <= stats.batch_requests <= K
    assert launches < K, "requests did not coalesce"


def test_batcher_mixed_workload_kinds_share_a_launch():
    """A trajectory plan and a flat query plan coalesce into one pool and
    each un-flattens through its own recipe."""
    tree = _tree(4)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    rs = np.random.RandomState(5)
    wps = rs.uniform(-1, 1, (4, 7)).astype(np.float32)
    traj = plan_trajectory(wps)
    obbs = random_obbs(jax.random.PRNGKey(6), 9)
    ref_traj = eng.execute(traj)[0]
    ref_q = eng.execute(plan_queries(obbs))[0]
    with RequestBatcher(eng, max_batch=4096, max_wait_ms=250.0) as b:
        t1 = b.submit(traj)
        t2 = b.submit(obbs)                  # OBBs shorthand
        v1, s1 = t1.result(timeout=120)
        v2, s2 = t2.result(timeout=120)
    assert v1.shape == (4,) and (v1 == ref_traj).all()
    assert (v2 == ref_q).all()
    if s1.batch_requests == 2:               # coalesced (timing-dependent)
        assert s1.batch_queries == traj.num_queries + obbs.n
        assert s1.pad_queries == _pad_bucket(s1.batch_queries) \
            - s1.batch_queries


def test_batcher_pad_accounting_and_rejections():
    tree = _tree(7, n=800, depth=3)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    obbs = random_obbs(jax.random.PRNGKey(8), 5)
    with RequestBatcher(eng, max_batch=64, max_wait_ms=1.0) as b:
        _, stats = b.submit(obbs).result(timeout=120)
        with pytest.raises(ValueError):      # grouped plan
            b.submit(plan_edges(obbs, np.zeros(5, np.int32), 1))
    assert stats.pad_queries == _pad_bucket(5) - 5
    assert b.totals.pad_queries >= stats.pad_queries
    assert b.totals.num_queries >= 5
    with pytest.raises(RuntimeError):        # closed
        b.submit(obbs)


# ---------------------------------------------------------------------------
# Serve harness
# ---------------------------------------------------------------------------

def test_run_service_reports_slos():
    from repro.launch.serve import SLO_METRICS, run_service
    tree = _tree(9, n=1500, depth=3)
    rep = run_service(tree, clients=2, requests=3, queries_per_request=4,
                      max_wait_ms=5.0, mode="wavefront_fused", seed=0)
    for metric in SLO_METRICS:
        assert rep[metric] > 0, metric
    assert rep["requests"] == 6 and rep["queries"] == 24
    assert rep["launches"] >= 1
    assert rep["p99_ms"] >= rep["p50_ms"]
    assert rep["counters"].num_queries >= 24


def test_engine_exports_typed_service_errors():
    """Satellite: clients catch service errors from ``repro.engine``
    without reaching into batcher internals."""
    import repro.engine as E
    for name in ("ServiceError", "Overloaded", "DeadlineExceeded",
                 "LaunchStalled", "WorkerDied", "BatcherClosed",
                 "DeviceLost", "RequestBatcher", "RequestStats",
                 "DEPTH_CAP_MODES"):
        assert name in E.__all__ and hasattr(E, name), name
    for err in (E.Overloaded, E.DeadlineExceeded, E.LaunchStalled,
                E.WorkerDied, E.BatcherClosed, E.DeviceLost):
        assert issubclass(err, E.ServiceError)
    assert not issubclass(E.ServiceError, ValueError)


# ---------------------------------------------------------------------------
# Depth-capped traversal (degraded mode substrate)
# ---------------------------------------------------------------------------

def test_depth_cap_conservative_superset_and_mode_agreement():
    """execute(max_depth=k) treats level-k cells as terminal: verdicts are
    a conservative SUPERSET of the exact ones (never a missed collision),
    identical across every DEPTH_CAP_MODES member, and full-depth
    max_depth is a no-op."""
    from repro.engine.executor import DEPTH_CAP_MODES
    tree = _tree(11)
    obbs = random_obbs(jax.random.PRNGKey(12), 64)
    plan = plan_queries(obbs)
    exact = np.asarray(CollisionEngine(
        tree, EngineConfig(mode="wavefront_fused")).execute(plan)[0])
    for k in (1, 2, tree.depth):
        capped = {}
        for mode in DEPTH_CAP_MODES:
            eng = CollisionEngine(tree, EngineConfig(mode=mode))
            assert eng.supports_depth_cap
            v, _ = eng.execute(plan, max_depth=k)
            capped[mode] = np.asarray(v)
            assert not (exact & ~capped[mode]).any(), (mode, k)
        ref = capped[DEPTH_CAP_MODES[0]]
        for mode, v in capped.items():
            assert (v == ref).all(), (mode, k)
        if k == tree.depth:
            assert (ref == exact).all()
    # Sharded capped equals single-device capped (shards=1 in-process).
    v1, _ = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused", shards=1)).execute(plan, max_depth=2)
    v0, _ = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused")).execute(plan, max_depth=2)
    assert (np.asarray(v1) == np.asarray(v0)).all()


def test_depth_cap_rejected_where_unsupported():
    tree = _tree(13, n=800, depth=3)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_persistent"))
    assert not eng.supports_depth_cap
    obbs = random_obbs(jax.random.PRNGKey(14), 4)
    with pytest.raises(ValueError, match="max_depth"):
        eng.execute(plan_queries(obbs), max_depth=1)
    eng2 = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    with pytest.raises(ValueError):
        eng2.execute(plan_queries(obbs), max_depth=0)


# ---------------------------------------------------------------------------
# Live rebind + elastic shard width (service v2)
# ---------------------------------------------------------------------------

def test_rebind_under_live_batcher_streaming_clients():
    """Satellite regression: swapping the bound scene while clients stream
    requests is safe — every verdict matches the request's queries against
    scene A or scene B (never a torn mix), rebind() is FIFO with the
    requests around it, and submits after it see scene B exactly."""
    tree_a = _tree(15, n=1200, depth=3)
    tree_b = _tree(16, n=1200, depth=3)
    cfg = EngineConfig(mode="wavefront_fused")
    ref_a = CollisionEngine(tree_a, cfg)
    ref_b = CollisionEngine(tree_b, cfg)
    n_clients, n_reqs = 3, 8
    reqs = [[random_obbs(jax.random.PRNGKey(100 * ci + ri), 4 + ri % 3)
             for ri in range(n_reqs)] for ci in range(n_clients)]
    refs = [[(np.asarray(ref_a.execute(plan_queries(o))[0]),
              np.asarray(ref_b.execute(plan_queries(o))[0]))
             for o in per_client] for per_client in reqs]

    live = CollisionEngine(tree_a, cfg)
    results = [[None] * n_reqs for _ in range(n_clients)]
    errors = []

    with RequestBatcher(live, max_wait_ms=1.0) as b:
        def client(ci):
            try:
                for ri in range(n_reqs):
                    v, _ = b.submit(reqs[ci][ri]).result(timeout=120)
                    results[ci][ri] = np.asarray(v)
            except BaseException as e:        # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        b.rebind(tree_b)                      # mid-stream, worker-routed
        probe = random_obbs(jax.random.PRNGKey(999), 6)
        v_after, _ = b.submit(probe).result(timeout=120)
        for t in threads:
            t.join()
    assert not errors, errors
    assert (np.asarray(v_after)
            == np.asarray(ref_b.execute(plan_queries(probe))[0])).all()
    for ci in range(n_clients):
        for ri in range(n_reqs):
            v = results[ci][ri]
            va, vb = refs[ci][ri]
            assert (v == va).all() or (v == vb).all(), (ci, ri)


def test_autoscale_widens_shards_under_load_on_eight_devices():
    """The elastic batcher scales EngineConfig.shards up between launches
    when p99 drifts past the SLO, and verdicts stay bitwise-correct
    across the rescale."""
    out = run_devices("""
    from repro.core.geometry import random_obbs
    from repro.core.octree import build_octree
    from repro.engine.batcher import RequestBatcher
    from repro.engine.executor import CollisionEngine, EngineConfig
    from repro.engine.plan import plan_queries

    rs = np.random.RandomState(0)
    tree = build_octree(rs.uniform(-1, 1, (1500, 3)).astype(np.float32),
                        depth=3)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused",
                                             shards=1))
    reqs = [random_obbs(jax.random.PRNGKey(i), 5 + i % 7)
            for i in range(14)]
    refs = [np.asarray(eng.execute(plan_queries(o))[0]) for o in reqs]
    with RequestBatcher(eng, max_wait_ms=1.0, autoscale_shards=True,
                        target_p99_ms=0.01) as b:   # unmeetable SLO
        for o, ref in zip(reqs, refs):
            v, _ = b.submit(o).result(timeout=120)
            assert (np.asarray(v) == ref).all()
    assert b.totals.shard_rescales >= 1, b.totals.shard_rescales
    assert eng.cfg.shards > 1, eng.cfg.shards
    print("AUTOSCALE_OK", eng.cfg.shards, b.totals.shard_rescales)
    """)
    assert "AUTOSCALE_OK" in out


def test_run_service_sharded_on_eight_devices():
    """The full service stack (shard_map engine under the batcher under
    concurrent clients) on 8 virtual devices."""
    out = run_devices("""
    from repro.core.octree import build_octree
    from repro.launch.serve import run_service

    rs = np.random.RandomState(0)
    tree = build_octree(rs.uniform(-1, 1, (1500, 3)).astype(np.float32),
                        depth=3)
    rep = run_service(tree, clients=2, requests=2, queries_per_request=4,
                      max_wait_ms=5.0, mode="wavefront_fused", shards=8)
    assert rep["requests"] == 4 and rep["qps"] > 0
    print("SERVE_SHARDED_OK", round(rep["p50_ms"], 3))
    """)
    assert "SERVE_SHARDED_OK" in out
