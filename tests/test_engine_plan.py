"""Plan layer: lowering round-trips, executor equivalence, payload lanes.

Covers the plan/execute split: every front-end shape must lower to the
canonical flat pool and un-flatten bit-exactly; grouped (owner/payload)
plans must agree with boolean plans reduced on the host; and the
payload-lane traverse/persist kernel variants run under interpret mode
against their jnp references, mirroring the other kernel suites.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import seeded_property

from repro.core.geometry import NUM_LINKS, OBBs, arm_link_obbs, random_obbs
from repro.core.octree import build_octree, device_octree
from repro.core.sact import PAYLOAD_INF
from repro.core.wavefront import CollisionEngine, EngineConfig
from repro.engine.plan import (QueryPlan, WORKLOADS, plan_batch, plan_edges,
                               plan_queries, plan_scenes, plan_trajectory)
from repro.kernels.persist.ops import traverse_whole
from repro.kernels.traverse.ops import traverse_step


def _tree(seed, n=4000, depth=4):
    rs = np.random.RandomState(seed)
    return build_octree(rs.uniform(-1, 1, (n, 3)).astype(np.float32),
                        depth=depth)


@seeded_property(max_examples=6)
def test_plan_lowering_roundtrips_bit_exactly(seed):
    """Every front-end shape -> flat pool -> unflatten, bit-exact."""
    rs = np.random.RandomState(seed % 100000)
    B, M = int(rs.randint(2, 6)), int(rs.randint(2, 8))
    obbs = random_obbs(jax.random.PRNGKey(seed % 100000), B * M)
    batch = OBBs(center=obbs.center.reshape(B, M, 3),
                 half=obbs.half.reshape(B, M, 3),
                 rot=obbs.rot.reshape(B, M, 3, 3))

    flat = plan_queries(obbs)
    assert flat.num_queries == B * M and flat.groups == B * M
    assert (np.asarray(flat.obb_c) == np.asarray(obbs.center)).all()

    pb = plan_batch(batch)
    assert pb.num_queries == B * M and pb.out_shape == (B, M)
    assert (np.asarray(pb.obb_c)
            == np.asarray(obbs.center)).all()          # row-major flatten
    assert (np.asarray(pb.obb_r).reshape(B, M, 3, 3)
            == np.asarray(batch.rot)).all()
    verdicts = rs.rand(B * M) < 0.5
    assert (pb.unflatten(verdicts) == verdicts.reshape(B, M)).all()

    ps = plan_scenes(batch)                            # (S, M) reading
    assert ps.num_scenes == B
    soq = np.asarray(ps.scene_of_query)
    assert (soq == np.repeat(np.arange(B), M)).all()
    assert (ps.unflatten(verdicts) == verdicts.reshape(B, M)).all()

    T = int(rs.randint(2, 6))
    wps = rs.uniform(-1, 1, (T, 7)).astype(np.float32)
    pt = plan_trajectory(jnp.asarray(wps))
    ref = arm_link_obbs(jnp.asarray(wps))
    assert pt.num_queries == T * NUM_LINKS
    assert (np.asarray(pt.obb_c) == np.asarray(ref.center)).all()
    link_hits = rs.rand(T * NUM_LINKS) < 0.3
    assert (pt.unflatten(link_hits)
            == link_hits.reshape(T, NUM_LINKS).any(axis=1)).all()


def test_plan_validation():
    obbs = random_obbs(jax.random.PRNGKey(0), 8)
    with pytest.raises(ValueError):
        QueryPlan(kind="nope", obb_c=obbs.center, obb_h=obbs.half,
                  obb_r=obbs.rot, out_shape=(8,))
    with pytest.raises(ValueError):
        QueryPlan(kind="queries", obb_c=obbs.center, obb_h=obbs.half,
                  obb_r=obbs.rot, out_shape=(4,))
    assert "edges" in WORKLOADS and "trajectory" in WORKLOADS


def test_query_front_ends_match_execute():
    tree = _tree(0)
    obbs = random_obbs(jax.random.PRNGKey(1), 24)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_fused"))
    got_q, cq = eng.query(obbs)
    got_e, ce = eng.execute(plan_queries(obbs))
    assert (got_q == got_e).all()
    assert cq.axis_tests_executed == ce.axis_tests_executed
    batch = OBBs(center=obbs.center.reshape(4, 6, 3),
                 half=obbs.half.reshape(4, 6, 3),
                 rot=obbs.rot.reshape(4, 6, 3, 3))
    got_b, _ = eng.query_batched(batch)
    assert (got_b == got_q.reshape(4, 6)).all()


def test_trajectory_plan_unifies_host_and_device():
    """check_trajectory's device_resident fork is gone: every mode consumes
    the same trajectory plan and agrees on flags AND work counters."""
    from repro.core.pipeline import check_trajectory
    tree = _tree(1)
    rs = np.random.RandomState(2)
    wps = jnp.asarray(rs.uniform(-1, 1, (5, 7)).astype(np.float32))
    res = {}
    for mode in ("wavefront_host", "wavefront", "wavefront_fused",
                 "wavefront_persistent"):
        res[mode] = check_trajectory(
            CollisionEngine(tree, EngineConfig(mode=mode)), wps)
    flags_ref, c_ref = res["wavefront"]
    assert flags_ref.shape == (5,)
    for mode, (flags, c) in res.items():
        assert (flags == flags_ref).all(), mode
        assert c.nodes_traversed == c_ref.nodes_traversed, mode
        assert c.axis_tests_executed == c_ref.axis_tests_executed, mode
        assert (c.exit_histogram == c_ref.exit_histogram).all(), mode


@pytest.mark.parametrize("mode", ["wavefront", "wavefront_fused",
                                  "wavefront_persistent"])
def test_grouped_plan_matches_boolean_plan_reduced_on_host(mode):
    """Owner/payload plans == boolean verdicts min-reduced per group: the
    in-traversal early exit may skip pairs but can never change the min."""
    tree = _tree(3)
    rs = np.random.RandomState(4)
    Q, G = 36, 9
    obbs = random_obbs(jax.random.PRNGKey(5), Q)
    owner = rs.randint(0, G, Q).astype(np.int32)
    owner[:G] = np.arange(G)                          # keep ids compact
    payload = rs.randint(0, 50, Q).astype(np.int32)
    eng = CollisionEngine(tree, EngineConfig(mode=mode))
    flat, _ = eng.execute(plan_queries(obbs))
    expect = np.full(G, PAYLOAD_INF, np.int64)
    np.minimum.at(expect, owner[flat], payload[flat].astype(np.int64))
    best, c = eng.execute(plan_edges(obbs, owner, G, payload=payload))
    assert best.shape == (G,)
    assert (best == expect).all()
    assert c.frontier_overflow == 0
    # owner-only plans give boolean-style group verdicts (payload zeros)
    hits, _ = eng.execute(plan_edges(obbs, owner, G))
    grp_any = np.zeros(G, bool)
    np.logical_or.at(grp_any, owner, flat)
    assert ((hits < PAYLOAD_INF) == grp_any).all()


def test_grouped_plan_rejected_on_host_modes():
    tree = _tree(3)
    obbs = random_obbs(jax.random.PRNGKey(5), 8)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_host"))
    with pytest.raises(ValueError):
        eng.execute(plan_edges(obbs, np.zeros(8, np.int32), 1))


def test_engine_scene_count_mismatch_rejected():
    tree = _tree(0, n=1000, depth=3)
    obbs = random_obbs(jax.random.PRNGKey(0), 8)
    batch = OBBs(center=obbs.center.reshape(2, 4, 3),
                 half=obbs.half.reshape(2, 4, 3),
                 rot=obbs.rot.reshape(2, 4, 3, 3))
    with pytest.raises(ValueError):
        CollisionEngine(tree, EngineConfig(mode="wavefront_fused")).execute(
            plan_scenes(batch))


@pytest.mark.parametrize("use_spheres", [False])
def test_traverse_step_payload_lane_interpret_matches_ref(use_spheres):
    """Payload-lane fused step: Pallas verdict kernel (interpret=True) and
    jnp arm agree on the grouped best, compacted frontier, and counters."""
    rs = np.random.RandomState(11)
    tree = _tree(11, n=2500, depth=4)
    dev = device_octree(tree)
    obbs = random_obbs(jax.random.PRNGKey(11), 24)
    G = 6
    owner = jnp.asarray(rs.randint(0, G, obbs.n).astype(np.int32))
    payload = jnp.asarray(rs.randint(0, 100, obbs.n).astype(np.int32))
    # Three kernel blocks of 1024 lanes: two live, the third retired.
    level, cap = 2, 3 * 1024
    n_l = len(tree.levels[level].codes)
    n_live = 1024 + 300
    idx = jnp.asarray(rs.randint(0, n_l, cap).astype(np.int32))
    q = jnp.asarray(rs.randint(0, obbs.n, cap).astype(np.int32))
    best0 = jnp.full((obbs.n,), PAYLOAD_INF, jnp.int32)
    args = (obbs.center, obbs.half, obbs.rot, dev, jnp.int32(level),
            jnp.int32(n_live), q, idx, best0)
    kw = dict(use_spheres=use_spheres, owner=owner, payload=payload)
    ref = traverse_step(*args, use_pallas=False, **kw)
    pal = traverse_step(*args, use_pallas=True, interpret=True, bn=1024, **kw)
    for name, a, b in zip(("cnt", "q_next", "idx_next", "best"),
                          ref[:4], pal[:4]):
        assert bool(jnp.all(a == b)), name
    assert ref[3].dtype == jnp.int32


def test_persist_kernel_payload_lane_interpret_matches_ref():
    """Payload-lane megakernel (identity owner): interpret-mode kernel ==
    jnp ref, best words and every stats field."""
    rs = np.random.RandomState(7)
    tree = _tree(7, n=2500, depth=3)
    dev = device_octree(tree)
    obbs = random_obbs(jax.random.PRNGKey(7), 21)     # 2 tiles at bq=16
    payload = jnp.asarray(rs.randint(0, 9, obbs.n).astype(np.int32))
    cap = 256
    ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=False, use_pallas=False, bq=16,
                         payload=payload)
    pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=False, use_pallas=True,
                         interpret=True, bq=16, payload=payload)
    assert ref[0].dtype == jnp.int32
    assert bool(jnp.all(ref[0] == pal[0]))
    for k in ref[1]:
        assert bool(jnp.all(ref[1][k] == pal[1][k])), k
    # payload semantics: best == payload where the boolean engine collides
    collide, _ = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                                use_spheres=False, use_pallas=False)
    best = np.asarray(ref[0])
    assert (best[np.asarray(collide)] == np.asarray(payload)[
        np.asarray(collide)]).all()
    assert (best[~np.asarray(collide)] == PAYLOAD_INF).all()
