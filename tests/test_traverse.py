"""Fused traversal step: CSR child table, kernel-vs-ref, engine equivalence.

The Pallas traversal-step kernel runs under ``interpret=True`` here so the
CPU CI matrix exercises kernel changes without a TPU.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import seeded_property

from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import (build_octree, device_octree, lookup_children,
                               node_centers_from_codes)
from repro.core.sact import sact_frontier, sact_frontier_staged
from repro.core.wavefront import CollisionEngine, EngineConfig
from repro.data.robotics import make_scene, scene_trajectories
from repro.kernels.traverse import ops as traverse_ops
from repro.kernels.traverse.ops import traverse_step
from repro.kernels.traverse.ref import traverse_test_ref

WORK_FIELDS = ("nodes_traversed", "leaf_tests", "axis_tests_executed",
               "axis_tests_decoded", "sphere_tests", "frontier_overflow")


def _random_tree(seed):
    rs = np.random.RandomState(seed % 100000)
    n = int(rs.randint(200, 3000))
    depth = int(rs.randint(2, 6))
    pts = rs.uniform(-1, 1, (n, 3)).astype(np.float32)
    return build_octree(pts, depth=depth), rs


@seeded_property(max_examples=10)
def test_csr_child_table_matches_searchsorted_probe(seed):
    """CSR (child_start, child_mask) == the searchsorted occupancy probe on
    random octrees: same occupied octants, same child positions."""
    tree, _ = _random_tree(seed)
    for level in range(tree.depth):
        lvl, nxt = tree.levels[level], tree.levels[level + 1]
        cand, idx = lookup_children(jnp.asarray(nxt.codes),
                                    jnp.asarray(lvl.codes))
        idx = np.asarray(idx)
        occupied = idx >= 0
        mask_bits = ((lvl.child_mask[:, None].astype(np.int32)
                      >> np.arange(8)) & 1).astype(bool)
        assert (mask_bits == occupied).all()
        # child index = start + popcount(mask & ((1 << j) - 1))
        below = (1 << np.arange(8)) - 1
        prefix = np.array([[bin(int(m) & int(b)).count("1") for b in below]
                           for m in lvl.child_mask], np.int32)
        csr_idx = lvl.child_start[:, None] + prefix
        assert (csr_idx[occupied] == idx[occupied]).all()
        # contiguity: popcounts partition the next level exactly
        counts = np.array([bin(int(m)).count("1") for m in lvl.child_mask])
        assert counts.sum() == len(nxt.codes)
        assert (lvl.child_start == np.cumsum(counts) - counts).all()


_one_shot_jit = jax.jit(sact_frontier, static_argnames=("use_spheres",))
_staged_jit = jax.jit(sact_frontier_staged, static_argnames=("use_spheres",))


@seeded_property(max_examples=6)
def test_two_phase_sact_matches_one_shot(seed):
    """sact_frontier_staged == sact_frontier bitwise, both sphere modes."""
    rs = np.random.RandomState(seed % 100000)
    k = 160                                   # fixed shape: one jit compile
    obbs = random_obbs(jax.random.PRNGKey(seed % 100000), k)
    node_c = jnp.asarray(rs.uniform(-1, 1, (k, 3)).astype(np.float32))
    node_h = jnp.asarray(rs.uniform(0.05, 0.6, (k, 3)).astype(np.float32))
    valid = jnp.asarray(rs.rand(k) < 0.8)
    for spheres in (False, True):
        a = _one_shot_jit(obbs.center, obbs.half, obbs.rot, node_c, node_h,
                          valid, use_spheres=spheres)
        b = _staged_jit(obbs.center, obbs.half, obbs.rot, node_c,
                        node_h, valid, use_spheres=spheres)
        for f in a._fields:
            assert bool(jnp.all(getattr(a, f) == getattr(b, f))), f


@pytest.mark.parametrize("use_spheres", [False, True])
@pytest.mark.parametrize("bn", [1024])
def test_traverse_kernel_interpret_matches_ref(use_spheres, bn):
    """Pallas traversal-step kernel (interpret=True) == jnp reference arm:
    packed verdicts, compacted next frontier, and work-model fields.  The
    frontier spans three ``bn`` blocks: the shallow levels leave the later
    blocks retired (past ``n_live``), the leaf level keeps all three live."""
    rs = np.random.RandomState(bn)
    pts = rs.uniform(-1, 1, (3000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    dev = device_octree(tree)
    obbs = random_obbs(jax.random.PRNGKey(bn), 24)
    for level in (1, 2, tree.depth):
        n_l = len(tree.levels[level].codes)
        cap = 3 * bn
        n_live = min(cap, max(n_l, 8))
        idx = rs.randint(0, n_l, cap).astype(np.int32)
        q = rs.randint(0, obbs.n, cap).astype(np.int32)
        args = (obbs.center, obbs.half, obbs.rot, dev, jnp.int32(level),
                jnp.int32(n_live), jnp.asarray(q), jnp.asarray(idx),
                jnp.zeros((obbs.n,), bool))
        ref = traverse_step(*args, use_spheres=use_spheres, use_pallas=False)
        pal = traverse_step(*args, use_spheres=use_spheres, use_pallas=True,
                            interpret=True, bn=bn)
        for name, a, b in zip(("cnt", "q_next", "idx_next", "collide"),
                              ref[:4], pal[:4]):
            assert bool(jnp.all(a == b)), (level, name)
        valid = np.asarray(ref[4]["valid"])
        assert (np.asarray(ref[4]["is_term"])[valid]
                == np.asarray(pal[4]["is_term"])[valid]).all()
        for f in ref[4]["res"]._fields:
            a, b = getattr(ref[4]["res"], f), getattr(pal[4]["res"], f)
            assert bool(jnp.all(a == b)), (level, f)


def test_traverse_packed_words_kernel_vs_ref_oracle():
    """The raw pallas_call's packed verdict words == the jnp oracle's, on
    a three-block frontier with two live blocks and one retired."""
    rs = np.random.RandomState(5)
    pts = rs.uniform(-1, 1, (2000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=3)
    obbs = random_obbs(jax.random.PRNGKey(5), 16)
    bn = 1024
    level, cap = 2, 3 * bn
    n_l = len(tree.levels[level].codes)
    n_live = bn + 300
    idx = rs.randint(0, n_l, cap)
    codes = jnp.asarray(tree.levels[level].codes[idx])
    full = jnp.asarray(tree.levels[level].full[idx])
    q = jnp.asarray(rs.randint(0, obbs.n, cap).astype(np.int32))
    cell = jnp.float32(tree.cell_size(level))
    lo = jnp.asarray(tree.scene_lo)
    node_c, node_h = node_centers_from_codes(codes, lo, cell)
    ref_packed = traverse_test_ref(obbs.center, obbs.half, obbs.rot, q,
                                   node_c, node_h, full, False, n_live,
                                   use_spheres=False)
    pal_packed = traverse_ops._test_pallas(
        obbs.center, obbs.half, obbs.rot, q, codes, full, cell, lo,
        jnp.bool_(False), jnp.int32(n_live), False, bn=bn, interpret=True)
    assert bool(jnp.all(ref_packed == pal_packed))


def test_fused_engine_bitwise_equivalence_on_bench_scenes():
    """wavefront_fused == wavefront == wavefront_host: verdicts AND work
    counters, on benchmark scenes (the fig11 acceptance criterion)."""
    for env, n_pts, depth in [("cubby", 4096, 4), ("dresser", 4096, 4)]:
        sc = make_scene(env, num_points=n_pts)
        tree = build_octree(sc.points, depth=depth)
        obbs = scene_trajectories(sc, num_trajectories=2, waypoints=6)
        res = {}
        for mode in ("wavefront_host", "wavefront", "wavefront_fused"):
            res[mode] = CollisionEngine(tree,
                                        EngineConfig(mode=mode)).query(obbs)
        ref_col, ref_c = res["wavefront"]
        for mode in ("wavefront_host", "wavefront_fused"):
            col, c = res[mode]
            assert (col == ref_col).all(), (env, mode)
            for f in WORK_FIELDS:
                assert getattr(c, f) == getattr(ref_c, f), (env, mode, f)
            assert c.nodes_per_level == ref_c.nodes_per_level, (env, mode)
            assert (c.exit_histogram == ref_c.exit_histogram).all(), (
                env, mode)
        # the fused step's bytes model must undercut the unfused arm
        assert res["wavefront_fused"][1].bytes_moved < ref_c.bytes_moved


def test_fused_engine_batched_and_spheres():
    """Fused engine under vmap (query_batched) and the sphere ablation."""
    rs = np.random.RandomState(9)
    pts = rs.uniform(-1, 1, (5000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(10), 48)
    batch = OBBs(center=obbs.center.reshape(6, 8, 3),
                 half=obbs.half.reshape(6, 8, 3),
                 rot=obbs.rot.reshape(6, 8, 3, 3))
    got_u, _ = CollisionEngine(tree, EngineConfig(
        mode="wavefront")).query_batched(batch)
    got_f, _ = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused")).query_batched(batch)
    assert (got_f == got_u).all()
    a, ca = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused", use_spheres=False)).query(obbs)
    b, cb = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused", use_spheres=True)).query(obbs)
    assert (a == b).all()
    assert cb.sphere_tests > 0
    assert cb.axis_tests_executed <= ca.axis_tests_executed


def test_fused_engine_kernel_arm_matches_ref_arm():
    """The fused engine with its Pallas step forced on (interpret=True)
    == the same engine on the jnp step: verdicts and every work counter.
    The pinned 3,072-lane frontier is three kernel blocks, so levels run
    with retired blocks and with several live ones."""
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)
    res = {arm: CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused", frontier_capacity=3 * 1024,
        use_pallas_traverse=arm)).query(obbs) for arm in (False, True)}
    (ref_col, ref_c), (col, c) = res[False], res[True]
    assert (col == ref_col).all()
    assert c.frontier_overflow == 0 and max(c.nodes_per_level) > 1024
    for f in WORK_FIELDS:
        assert getattr(c, f) == getattr(ref_c, f), f
    assert c.nodes_per_level == ref_c.nodes_per_level
    assert (c.exit_histogram == ref_c.exit_histogram).all()
