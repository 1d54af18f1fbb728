"""Octree build invariants + engine-variant equivalence to the naive arm."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import build_octree, morton_decode, morton_encode
from repro.core.wavefront import (MODES, CollisionEngine, EngineConfig,
                                  query_batched_scenes)
from repro.data.robotics import make_scene, scene_trajectories


def test_morton_roundtrip():
    rs = np.random.RandomState(0)
    xyz = rs.randint(0, 1 << 10, (1000, 3)).astype(np.uint32)
    codes = morton_encode(xyz[:, 0], xyz[:, 1], xyz[:, 2])
    x, y, z = morton_decode(codes)
    assert (x == xyz[:, 0]).all() and (y == xyz[:, 1]).all() \
        and (z == xyz[:, 2]).all()


def test_octree_levels_consistent():
    rs = np.random.RandomState(1)
    pts = rs.uniform(-1, 1, (5000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=5)
    # every point falls inside some leaf AABB
    leaves = tree.leaf_aabbs()
    lo = np.asarray(leaves.center) - np.asarray(leaves.half)
    hi = np.asarray(leaves.center) + np.asarray(leaves.half)
    eps = 1e-5
    for p in pts[::97]:
        inside = ((p >= lo - eps) & (p <= hi + eps)).all(-1).any()
        assert inside
    # parent of every occupied node exists at the previous level
    for l in range(1, tree.depth + 1):
        parents = set((tree.levels[l].codes >> np.uint32(3)).tolist())
        assert parents <= set(tree.levels[l - 1].codes.tolist())
    # point ranges partition the cloud
    assert tree.leaf_point_count.sum() == len(pts)


def test_full_flags():
    # a solid dense block of points -> interior nodes become full
    g = np.stack(np.meshgrid(*[np.linspace(0.01, 0.99, 64)] * 3,
                             indexing="ij"), -1).reshape(-1, 3)
    tree = build_octree(g.astype(np.float32), depth=4,
                        scene_lo=np.zeros(3, np.float32), scene_size=1.0)
    # at depth 4 every cell holds points -> every level is fully occupied
    assert tree.levels[0].full.all()
    assert all(l.full.all() for l in tree.levels)


@pytest.mark.parametrize("mode", [m for m in MODES if m != "naive"])
def test_engine_matches_naive(mode):
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)
    ref, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    got, c = CollisionEngine(tree, EngineConfig(mode=mode)).query(obbs)
    assert (got == ref).all()
    assert c.frontier_overflow == 0


def test_engine_spheres_ablation_matches():
    rs = np.random.RandomState(4)
    pts = rs.uniform(-1, 1, (6000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(5), 30)
    a, ca = CollisionEngine(tree, EngineConfig(
        mode="wavefront", use_spheres=False)).query(obbs)
    b, cb = CollisionEngine(tree, EngineConfig(
        mode="wavefront", use_spheres=True)).query(obbs)
    assert (a == b).all()
    assert cb.sphere_tests > 0
    assert cb.axis_tests_executed <= ca.axis_tests_executed


def test_work_model_orderings():
    """Tree < naive in tests; early-exit executes fewer axis tests."""
    rs = np.random.RandomState(6)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(7), 32)
    _, c_naive = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    _, c_tta = CollisionEngine(tree, EngineConfig(
        mode="staged_noexit")).query(obbs)
    _, c_wf = CollisionEngine(tree, EngineConfig(mode="wavefront")).query(obbs)
    assert c_tta.nodes_traversed < c_naive.nodes_traversed
    assert c_wf.axis_tests_executed <= c_tta.axis_tests_executed
    assert c_wf.axis_tests_executed < c_wf.axis_tests_decoded
    # fused bytes model < unfused
    _, c_fu = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused")).query(obbs)
    assert c_fu.bytes_moved < c_wf.bytes_moved


def test_device_engine_matches_host_bitwise():
    """Device-resident while_loop traversal == legacy host-loop engine,
    verdicts AND work counters, on the seed test scenes."""
    for seed, n_pts, depth, n_obb in [(2, 8000, 4, 40), (8, 5000, 5, 24)]:
        rs = np.random.RandomState(seed)
        pts = rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32)
        tree = build_octree(pts, depth=depth)
        obbs = random_obbs(jax.random.PRNGKey(seed), n_obb)
        host, ch = CollisionEngine(
            tree, EngineConfig(mode="wavefront_host")).query(obbs)
        dev, cd = CollisionEngine(
            tree, EngineConfig(mode="wavefront")).query(obbs)
        assert (dev == host).all()
        assert cd.nodes_traversed == ch.nodes_traversed
        assert cd.axis_tests_executed == ch.axis_tests_executed
        assert cd.leaf_tests == ch.leaf_tests
        assert cd.nodes_per_level == ch.nodes_per_level
        assert (cd.exit_histogram == ch.exit_histogram).all()
        assert cd.frontier_overflow == 0


def _as_batch(obbs: OBBs, b: int) -> OBBs:
    m = obbs.n // b
    return OBBs(center=obbs.center.reshape(b, m, 3),
                half=obbs.half.reshape(b, m, 3),
                rot=obbs.rot.reshape(b, m, 3, 3))


def test_query_batched_matches_per_set_queries():
    rs = np.random.RandomState(3)
    pts = rs.uniform(-1, 1, (6000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(4), 48)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront"))
    batch = _as_batch(obbs, 6)                       # (6, 8) query sets
    got, c = eng.query_batched(batch)
    assert got.shape == (6, 8)
    flat, _ = eng.query(obbs)
    assert (got.reshape(-1) == flat).all()
    assert c.num_queries == 48
    # host fallback loop agrees with the single-call device path
    host = CollisionEngine(tree, EngineConfig(mode="wavefront_host"))
    got_h, _ = host.query_batched(batch)
    assert (got_h == got).all()


def test_query_batched_scenes_single_call():
    trees, sets = [], []
    for seed in (11, 12):
        rs = np.random.RandomState(seed)
        pts = rs.uniform(-1, 1, (4000, 3)).astype(np.float32)
        trees.append(build_octree(pts, depth=4))
        sets.append(random_obbs(jax.random.PRNGKey(seed), 20))
    stack = OBBs(center=jnp.stack([o.center for o in sets]),
                 half=jnp.stack([o.half for o in sets]),
                 rot=jnp.stack([o.rot for o in sets]))
    got, c = query_batched_scenes(trees, stack)
    assert got.shape == (2, 20)
    for s in range(2):
        ref, _ = CollisionEngine(trees[s],
                                 EngineConfig(mode="naive")).query(sets[s])
        assert (got[s] == ref).all()
    assert c.num_queries == 40


def test_device_engine_capacity_escalation():
    """A deliberately tiny initial bucket must escalate, not drop work."""
    rs = np.random.RandomState(5)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(6), 40)
    ref, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    got, c = CollisionEngine(tree, EngineConfig(
        mode="wavefront", min_bucket=64)).query(obbs)
    assert (got == ref).all()
    assert c.frontier_overflow == 0


def test_scene_traversal_on_synthetic_cubby():
    scene = make_scene("cubby", num_points=30000)
    tree = build_octree(scene.points, depth=5)
    obbs = scene_trajectories(scene, num_trajectories=3, waypoints=10)
    ref, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    got, c = CollisionEngine(tree, EngineConfig(mode="wavefront")).query(obbs)
    assert (got == ref).all()
    assert 0 < int(ref.sum()) < obbs.n           # some but not all collide


def test_scene_is_fixed_by_its_seed():
    """make_scene derives its RNG seed from the environment's index, not
    from Python's per-process salted string hash: the same seed gives the
    same points in every process."""
    import hashlib
    pts = make_scene("cubby", 0, 4096).points
    assert hashlib.sha256(pts.tobytes()).hexdigest() == (
        "92fd5ddfafc9ac06a8d313056a6a9dd925283451b94e6e4bd0fea23b24daa3cf")
    with pytest.raises(ValueError):
        make_scene("no_such_scene", 0, 16)
