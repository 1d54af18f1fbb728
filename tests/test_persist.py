"""Persistent whole-traversal megakernel: engine equivalence, interpret-mode
kernel vs ref, overflow counting, ragged multi-scene frontier, escalation policy,
and the traversal jit cache.

The Pallas megakernel runs under ``interpret=True`` here so the CPU CI
matrix exercises the kernel body without a TPU, mirroring the
kernels/traverse setup.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.core.geometry import OBBs, random_obbs
from repro.core.octree import (align_rows, build_octree,
                               concat_device_octrees, device_octree)
from repro.core.quantize import META_FORMATS
from repro.core.sact import PAYLOAD_INF
from repro.core.wavefront import (MODES, CollisionEngine, EngineConfig,
                                  query_batched_scenes, traversal_cache_info)
from repro.data.robotics import make_scene, scene_trajectories
from repro.kernels.persist.ops import (META_LAYOUTS, SUB_WINDOW_ROWS,
                                       choose_meta_layout, meta_stream_bytes,
                                       meta_table_bytes, sub_window_rows,
                                       traverse_whole)
from repro.kernels.persist.kernel import byte_planes, onehot_gather
from repro.kernels.persist.ref import frontier_widths

WORK_FIELDS = ("nodes_traversed", "leaf_tests", "axis_tests_executed",
               "axis_tests_decoded", "sphere_tests", "frontier_overflow")


def _assert_counters_equal(c, ref_c, ctx):
    for f in WORK_FIELDS:
        assert getattr(c, f) == getattr(ref_c, f), (ctx, f)
    assert c.nodes_per_level == ref_c.nodes_per_level, ctx
    assert (c.exit_histogram == ref_c.exit_histogram).all(), ctx


def test_frontier_widths():
    assert frontier_widths(2048, w_min=128) == (128, 256, 512, 1024, 2048)
    assert frontier_widths(128, w_min=128) == (128,)
    assert frontier_widths(64, w_min=128) == (64,)
    assert frontier_widths(96, w_min=32) == (32, 64, 96)


def test_persistent_engine_bitwise_equivalence_on_bench_scenes():
    """wavefront_persistent == wavefront_fused == wavefront: verdicts AND
    work counters, on benchmark scenes (the acceptance criterion)."""
    for env, n_pts, depth in [("cubby", 4096, 4), ("dresser", 4096, 4)]:
        sc = make_scene(env, num_points=n_pts)
        tree = build_octree(sc.points, depth=depth)
        obbs = scene_trajectories(sc, num_trajectories=2, waypoints=6)
        res = {}
        for mode in ("wavefront", "wavefront_fused", "wavefront_persistent"):
            res[mode] = CollisionEngine(tree,
                                        EngineConfig(mode=mode)).query(obbs)
        ref_col, ref_c = res["wavefront_fused"]
        col, c = res["wavefront_persistent"]
        assert (col == ref_col).all(), env
        _assert_counters_equal(c, ref_c, env)
        _assert_counters_equal(res["wavefront"][1], ref_c, env)
        # persistent bytes model (per query, not per pair-level) undercuts
        # the fused step's frontier round trips
        assert c.bytes_moved < ref_c.bytes_moved


@pytest.mark.parametrize("fmt", META_FORMATS)
@pytest.mark.parametrize("use_spheres", [False, True])
def test_persist_kernel_interpret_matches_ref(use_spheres, fmt):
    """Pallas megakernel (interpret=True, multiple query tiles) == jnp ref:
    verdicts and every stats field — the gather count included — bitwise,
    in every row format."""
    rs = np.random.RandomState(7)
    pts = rs.uniform(-1, 1, (2500, 3)).astype(np.float32)
    tree = build_octree(pts, depth=3)
    dev = device_octree(tree, meta_format=fmt)
    obbs = random_obbs(jax.random.PRNGKey(7), 21)     # 2 tiles at bq=16
    cap = 256
    ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=use_spheres, use_pallas=False, bq=16)
    pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=use_spheres, use_pallas=True,
                         interpret=True, bq=16)
    assert bool(jnp.all(ref[0] == pal[0]))
    for k in ref[1]:
        assert bool(jnp.all(ref[1][k] == pal[1][k])), k
    assert int(ref[1]["meta_gathers"]) > 0


#: int32 words whose bytes take 0x00, 0x7F, 0x80 and 0xFF, INT32_MIN, -1
#: and PAYLOAD_INF; f32 bit patterns of -0.0, the smallest denormal, +-inf
#: and NaNs with payloads.
_I32_WORDS = (0, 0x7F7F7F7F, -0x7F7F7F80, -1, -(1 << 31), PAYLOAD_INF,
              0x00FF807F, -0x7F80FF01)
_F32_BITS = (-(1 << 31), 1, 0x7F800000, -0x800000, 0x7FC12345, 0x7F800001,
             -0x3EDCBA9, 0x3F800000)


@pytest.mark.parametrize("layout", ["window", "flat", "transposed"])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
def test_onehot_gather_is_bit_exact(dtype, layout):
    """The megakernel's MXU gather (interpret=True) returns every picked
    word bit for bit, as a numpy gather does: special int32 and f32 bit
    patterns, window rows 0, 127, 128 and 1023 through the sheet pick, and
    zero for lanes whose selector column is all false."""
    R, C = 8, 256
    K = 1024 if layout == "window" else 128
    rs = np.random.RandomState(5)
    bits = rs.randint(-(1 << 31), (1 << 31) - 1, (R, K), dtype=np.int64)
    special = _I32_WORDS if dtype == "int32" else _F32_BITS
    rows = (0, 127, 128, 1023) if layout == "window" else (0, 1, 127)
    for j, row in enumerate(rows):
        bits[:, row] = np.roll(special, j)[:R]
    bits = bits.astype(np.int32)
    # Lanes pick the special rows, random rows, and nothing (-1, K).
    idx = rs.randint(0, K, C).astype(np.int32)
    idx[:len(rows)] = rows
    idx[len(rows):len(rows) + 2] = (-1, K)
    vals = bits.view(np.float32) if dtype == "float32" else bits
    sheets = vals.reshape(R, K // 128, 128)

    def kernel(v_ref, i_ref, o_ref):
        v = v_ref[...]
        if dtype == "float32":
            v = jax.lax.bitcast_convert_type(v, jnp.int32)
        i = i_ref[...]
        if layout == "window":
            onehot = (i & 127) == jax.lax.broadcasted_iota(
                jnp.int32, (128, C), 0)
            got = onehot_gather(byte_planes(v.reshape(R * 8, 128)), onehot,
                                nseg=8, sheet=i >> 7)
        elif layout == "flat":
            got = onehot_gather(byte_planes(v[:, 0]), i == jax.lax.
                                broadcasted_iota(jnp.int32, (K, C), 0))
        else:
            got = onehot_gather(byte_planes(v[:, 0]), i.T == jax.lax.
                                broadcasted_iota(jnp.int32, (C, K), 1),
                                rhs_t=True)
        if dtype == "float32":
            got = jax.lax.bitcast_convert_type(got, jnp.float32)
        o_ref[...] = got

    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((R, C), vals.dtype),
        interpret=True)(jnp.asarray(sheets), jnp.asarray(idx[None, :]))
    picked = (idx >= 0) & (idx < K)
    want = np.where(picked, bits[:, np.clip(idx, 0, K - 1)], 0)
    assert (np.asarray(out).view(np.int32) == want).all()


def test_persist_kernel_spill_ring_counts_overflow():
    """A deliberately tiny VMEM frontier must overflow: the kernel reports
    the same overflow count as the global-pool ref (single tile == one
    pool)."""
    rs = np.random.RandomState(3)
    pts = rs.uniform(-1, 1, (4000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    dev = device_octree(tree)
    obbs = random_obbs(jax.random.PRNGKey(3), 24)
    cap = 64                                     # << peak frontier
    ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=False, use_pallas=False)
    pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_spheres=False, use_pallas=True,
                         interpret=True, bq=32)  # one tile: global == tile
    assert int(ref[1]["overflow"]) > 0
    assert int(pal[1]["overflow"]) == int(ref[1]["overflow"])


def test_persistent_escalation_replays_until_exact():
    """A tiny initial bucket must climb the escalation ladder (>= 2
    replays), end with zero overflow, and report exact verdicts."""
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)
    ref, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    eng = CollisionEngine(tree, EngineConfig(mode="wavefront_persistent",
                                             min_bucket=32))
    got, c = eng.query(obbs)
    assert (got == ref).all()
    assert c.frontier_overflow == 0
    assert c.escalations >= 2
    # The engine remembers the clean capacity: a repeat query pays zero
    # replays (and, per traversal_cache_info, zero retraces).
    got2, c2 = eng.query(obbs)
    assert (got2 == ref).all()
    assert c2.escalations == 0


@pytest.fixture
def tile_frontier_cap(monkeypatch):
    """Lower the megakernel's per-tile frontier cap to 256 lanes; the
    traversal cache is cleared around the test so no program traced at
    the real cap is reused, and none traced at 256 outlives it."""
    from repro.engine import executor
    from repro.kernels.persist import ops
    executor._traversal_fn.cache_clear()
    monkeypatch.setattr(ops, "MAX_TILE_FRONTIER", 256)
    yield 256
    executor._traversal_fn.cache_clear()


def test_tile_frontier_cap_routes_to_ref_arm_exactly(tile_frontier_cap,
                                                     caplog):
    """A tile whose frontier outgrows the megakernel's per-tile cap cannot
    be helped by more capacity: the engine finishes the ladder on the ref
    arm (warning + ``ref_arm_fallbacks``), with zero overflow and verdicts
    and work counters equal to the ref arm's own run.  A repeat plan
    starts on the ref arm."""
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)   # peak frontier 1088
    naive, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    ref, ref_c = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", min_bucket=32,
        use_pallas_traverse=False)).query(obbs)
    eng = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", min_bucket=32,
        use_pallas_traverse=True))
    with caplog.at_level("WARNING", logger="repro.engine.executor"):
        got, c = eng.query(obbs)
    assert "tile frontier" in caplog.text
    assert c.ref_arm_fallbacks == 1 and c.frontier_overflow == 0
    assert (got == ref).all() and (got == naive).all()
    _assert_counters_equal(c, ref_c, "tile-frontier fallback")
    got2, c2 = eng.query(obbs)
    assert (got2 == ref).all()
    assert c2.ref_arm_fallbacks == 1 and c2.escalations == 0


def test_persistent_max_frontier_clamp_underapproximates():
    """At the max_frontier clamp the engine cannot escalate further: the
    overflow count is reported and verdicts under-approximate (drops can
    only lose collisions, never invent them)."""
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (8000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)
    ref, _ = CollisionEngine(tree, EngineConfig(mode="naive")).query(obbs)
    got, c = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent", max_frontier=256)).query(obbs)
    assert c.frontier_overflow > 0
    assert not (got & ~ref).any()            # no false positives
    assert got.sum() <= ref.sum()


def test_query_batched_persistent_flattens_to_one_pool():
    """query_batched under the persistent mode (flat ragged pool, no vmap)
    == the fused vmapped arm, verdicts and aggregate work counters."""
    rs = np.random.RandomState(9)
    pts = rs.uniform(-1, 1, (5000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=4)
    obbs = random_obbs(jax.random.PRNGKey(10), 48)
    batch = OBBs(center=obbs.center.reshape(6, 8, 3),
                 half=obbs.half.reshape(6, 8, 3),
                 rot=obbs.rot.reshape(6, 8, 3, 3))
    got_f, cf = CollisionEngine(tree, EngineConfig(
        mode="wavefront_fused")).query_batched(batch)
    got_p, cp = CollisionEngine(tree, EngineConfig(
        mode="wavefront_persistent")).query_batched(batch)
    assert got_p.shape == (6, 8)
    assert (got_p == got_f).all()
    _assert_counters_equal(cp, cf, "batched")
    assert cp.num_queries == 48


def test_ragged_scenes_mixed_sizes_one_call():
    """Mixed-size scenes through the ragged flat frontier: verdicts match
    per-scene naive queries and aggregate counters match the sum of
    per-scene persistent queries."""
    trees, sets = [], []
    for seed, n_pts in ((11, 1000), (12, 12000), (13, 4000)):
        rs = np.random.RandomState(seed)
        pts = rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32)
        trees.append(build_octree(pts, depth=4))
        sets.append(random_obbs(jax.random.PRNGKey(seed), 20))
    stack = OBBs(center=jnp.stack([o.center for o in sets]),
                 half=jnp.stack([o.half for o in sets]),
                 rot=jnp.stack([o.rot for o in sets]))
    for mode in ("wavefront_fused", "wavefront_persistent"):
        got, c = query_batched_scenes(trees, stack, EngineConfig(mode=mode))
        assert got.shape == (3, 20)
        for s in range(3):
            ref, _ = CollisionEngine(trees[s],
                                     EngineConfig(mode="naive")).query(sets[s])
            assert (got[s] == ref).all(), (mode, s)
        assert c.num_queries == 60
    # counters are the sum of independent per-scene traversals
    per_scene = [CollisionEngine(t, EngineConfig(
        mode="wavefront_persistent")).query(o) for t, o in zip(trees, sets)]
    _, cr = query_batched_scenes(trees, stack,
                                 EngineConfig(mode="wavefront_persistent"))
    for f in ("nodes_traversed", "leaf_tests", "axis_tests_executed",
              "sphere_tests"):
        assert getattr(cr, f) == sum(getattr(c, f) for _, c in per_scene), f


def test_ragged_concat_table_roots_and_counts():
    trees = []
    for seed, n_pts in ((1, 500), (2, 6000)):
        rs = np.random.RandomState(seed)
        trees.append(build_octree(
            rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32), depth=3))
    multi = concat_device_octrees(trees)
    counts = np.asarray(multi.counts)
    for l in range(4):
        assert counts[l] == sum(len(t.levels[l].codes) for t in trees)
    # scene s's root is flat node s of the level-0 row
    meta0 = np.asarray(multi.node_meta[0])
    assert (meta0[:2, 0].view(np.uint32) == 0).all()
    # flat table holds the total (DMA-chunk aligned), not S x widest
    assert multi.node_meta.shape[1] == align_rows(max(counts))


def test_engineconfig_rejects_unknown_mode():
    with pytest.raises(ValueError) as ei:
        EngineConfig(mode="warpfront")
    msg = str(ei.value)
    assert "warpfront" in msg
    for mode in MODES:
        assert mode in msg


def _slab_scene(seed=3, n_pts=4000, depth=5):
    """Sparse slab: a real multi-level traversal (root never full)."""
    rs = np.random.RandomState(seed)
    pts = rs.uniform(-1, 1, (n_pts, 3)).astype(np.float32)
    return build_octree(pts[np.abs(pts[:, 2]) < 0.3], depth=depth)


@pytest.mark.parametrize("fmt", META_FORMATS)
def test_streamed_kernel_interpret_matches_ref_and_resident(fmt):
    """Streamed metadata windows (interpret-mode DMA machinery, multiple
    query tiles) == streamed jnp ref on EVERY stats field including the
    meta_rows window schedule and the gather count; the resident kernel
    == the resident ref likewise, and == the streamed layout on everything
    but its two schedule counters (the layout cannot change work, only
    traffic and the windows gathered from)."""
    dev = device_octree(_slab_scene(), meta_format=fmt)
    obbs = random_obbs(jax.random.PRNGKey(3), 37)     # 3 tiles at bq=16
    cap = 2048
    kw = dict(use_spheres=False, bq=16)
    ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_pallas=False, streamed=True, **kw)
    pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_pallas=True, interpret=True, streamed=True,
                         **kw)
    res_ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                             use_pallas=False, streamed=False, **kw)
    res = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                         use_pallas=True, interpret=True, streamed=False,
                         **kw)
    assert int(ref[1]["meta_rows"]) > 0
    assert int(ref[1]["meta_gathers"]) > 0
    assert bool(jnp.all(ref[0] == pal[0]))
    for k in ref[1]:
        assert bool(jnp.all(ref[1][k] == pal[1][k])), k
    assert int(res[1]["meta_rows"]) == 0
    assert bool(jnp.all(res[0] == pal[0]))
    for k in ref[1]:
        assert bool(jnp.all(res_ref[1][k] == res[1][k])), k
        if k not in ("meta_rows", "meta_gathers"):
            assert bool(jnp.all(res[1][k] == pal[1][k])), k


def test_bigscene_streamed_engine_bitwise_vs_fused():
    """The satellite acceptance run: a scene >= 4x the VMEM residency
    limit stays under mode="wavefront_persistent" (streamed layout, no
    fused fallback), with the interpret-mode megakernel's verdicts AND
    work counters bitwise-identical to wavefront_fused and to the jnp
    ref arm."""
    tree = _slab_scene()
    n_max = max(len(l.codes) for l in tree.levels)
    table = meta_table_bytes(tree.depth, n_max)
    # the residency limit IS the budget: table // 4 puts this scene at
    # 4x the limit.  The estimator must flip exactly there — resident at
    # a table-sized budget, streamed below it — or the test is not
    # exercising the streamed arm at all.
    budget = table // 4
    # (fmt pinned to fp32: the free chooser would instead COMPRESS its way
    # back under this budget — resident u8 — which test_quantize covers)
    assert choose_meta_layout(tree.depth, n_max, budget,
                              fmt="fp32").layout == "streamed"
    assert choose_meta_layout(tree.depth, n_max, table,
                              fmt="fp32").layout == "resident"
    obbs = random_obbs(jax.random.PRNGKey(5), 24)
    ref_col, ref_c = CollisionEngine(
        tree, EngineConfig(mode="wavefront_fused")).query(obbs)
    engines = {
        "kernel": EngineConfig(mode="wavefront_persistent",
                               vmem_budget=budget, meta_format="fp32",
                               use_pallas_traverse=True),
        "ref": EngineConfig(mode="wavefront_persistent",
                            vmem_budget=budget, meta_format="fp32"),
    }
    counters = {}
    for name, cfg in engines.items():
        eng = CollisionEngine(tree, cfg)
        assert eng.meta_layout == "streamed"
        col, c = eng.query(obbs)
        assert (col == ref_col).all(), name
        _assert_counters_equal(c, ref_c, name)
        assert c.meta_rows_streamed > 0, name
        counters[name] = c
    # kernel and ref arms agree on the window schedule itself
    assert (counters["kernel"].meta_rows_streamed
            == counters["ref"].meta_rows_streamed)
    # streamed metadata traffic is priced into the persistent bytes model
    assert counters["kernel"].bytes_moved > 0


def test_residency_estimator_and_override():
    """choose_meta_layout picks by table size vs budget; EngineConfig can
    pin either layout; verdicts and work counters never depend on it."""
    tree = _slab_scene()
    n_max = max(len(l.codes) for l in tree.levels)
    table = meta_table_bytes(tree.depth, n_max)
    assert choose_meta_layout(tree.depth, n_max, budget=table,
                              fmt="fp32").layout == "resident"
    assert choose_meta_layout(tree.depth, n_max, budget=table - 1,
                              fmt="fp32").layout == "streamed"
    assert set(META_LAYOUTS) == {"resident", "streamed"}
    # the streamed ping/pong pair holds two FIXED-SIZE sub-level windows
    # (plus one 8-row DMA chunk of slack each): its VMEM cost is fully
    # decoupled from n_max — a 16x wider table streams through the same
    # scratch — and a table narrower than one window shrinks the pair.
    assert meta_stream_bytes(1 << 20) == meta_stream_bytes(1 << 24)
    assert sub_window_rows(1 << 20) == SUB_WINDOW_ROWS
    assert meta_stream_bytes(n_max) <= meta_stream_bytes(1 << 20)
    assert meta_stream_bytes(64) < meta_stream_bytes(1 << 20)
    obbs = random_obbs(jax.random.PRNGKey(9), 24)
    runs = {}
    for layout, stream in (("resident", False), ("streamed", True)):
        eng = CollisionEngine(tree, EngineConfig(
            mode="wavefront_persistent", stream_meta=stream))
        assert eng.meta_layout == layout
        runs[layout] = eng.query(obbs)
    col_r, c_r = runs["resident"]
    col_s, c_s = runs["streamed"]
    assert (col_r == col_s).all()
    _assert_counters_equal(c_s, c_r, "layouts")
    assert c_r.meta_rows_streamed == 0
    assert c_s.meta_rows_streamed > 0
    assert c_s.bytes_moved > c_r.bytes_moved


def test_owner_tiled_streamed_kernel_matches_ref():
    """Cross-slot owner (swept-edge) plans run owner-group tiled on the
    megakernel under BOTH metadata layouts: verdicts and every stats
    field — including the streamed window schedule's meta_rows — bitwise
    kernel == ref, and the streamed layout actually models traffic (the
    old ref-only routing pinned these plans resident)."""
    dev = device_octree(_slab_scene())
    obbs = random_obbs(jax.random.PRNGKey(2), 24)
    owner = jnp.asarray(np.repeat(np.arange(3), 8), jnp.int32)
    payload = jnp.asarray(np.tile(np.arange(8), 3), jnp.int32)
    kw = dict(use_spheres=False, owner_of_query=owner, payload=payload,
              bq=8)
    for streamed in (False, True):
        ref = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, 512,
                             use_pallas=False, streamed=streamed, **kw)
        pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, 512,
                             use_pallas=True, interpret=True,
                             streamed=streamed, **kw)
        assert bool(jnp.all(ref[0][:3] == pal[0][:3])), streamed
        for k in ref[1]:
            assert bool(jnp.all(ref[1][k] == pal[1][k])), (streamed, k)
        assert int(ref[1]["meta_rows"]) > 0 if streamed \
            else int(ref[1]["meta_rows"]) == 0


def test_cap_memo_rekeys_on_scene_growth():
    """Growing a scene between calls (rebind_octrees) must re-enter the
    escalation ladder: the clean-capacity memo keys on the scene node
    counts, so the old scene's (too small) clean capacity is never
    reused and the first query against the grown scene still ends
    overflow-free and exact."""
    rs = np.random.RandomState(6)
    small = build_octree(
        rs.uniform(-1, 1, (300, 3)).astype(np.float32), depth=4)
    big = build_octree(
        rs.uniform(-1, 1, (8000, 3)).astype(np.float32), depth=4)
    obbs = random_obbs(jax.random.PRNGKey(3), 40)
    eng = CollisionEngine(small, EngineConfig(mode="wavefront_persistent",
                                              min_bucket=32))
    eng.query(obbs)
    (old_key,) = set(eng._cap_memo)
    eng.rebind_octrees(big)
    # superseded-scene entries are unreadable (sig-keyed) and pruned
    assert not eng._cap_memo
    ref, _ = CollisionEngine(big, EngineConfig(mode="naive")).query(obbs)
    got, c = eng.query(obbs)
    assert (got == ref).all()
    assert c.frontier_overflow == 0
    assert c.escalations >= 1          # ladder re-entered, not memo-skipped
    # same query shape, new scene signature in the key
    (new_key,) = set(eng._cap_memo)
    assert old_key[:-1] == new_key[:-1] and old_key[-1] != new_key[-1]


def test_traversal_cache_survives_engine_reconstruction():
    """A fresh CollisionEngine on a same-shaped scene reuses the traced
    traversal: the per-key trace counts do not grow."""
    rs = np.random.RandomState(4)
    pts = rs.uniform(-1, 1, (3000, 3)).astype(np.float32)
    obbs = random_obbs(jax.random.PRNGKey(4), 16)
    tree1 = build_octree(pts, depth=3)
    eng1 = CollisionEngine(tree1, EngineConfig(mode="wavefront_persistent"))
    eng1.query(obbs)
    traces_before = traversal_cache_info()["traces"]
    # new engine, new device arrays, same shapes -> no retrace
    tree2 = build_octree(pts, depth=3)
    eng2 = CollisionEngine(tree2, EngineConfig(mode="wavefront_persistent"))
    got, _ = eng2.query(obbs)
    traces_after = traversal_cache_info()["traces"]
    for key, n in traces_before.items():
        assert traces_after[key] == n, key
    assert traversal_cache_info()["hits"] > 0
