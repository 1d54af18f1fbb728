"""Quantized node-metadata formats (repro.core.quantize, DESIGN.md §3).

Soundness is enforced two ways, per the compression contract:

1. containment properties — outward-rounded u8/bf16 bounds always contain
   the fp32 bounds, degenerate thin boxes included (hypothesis-style via
   ``seeded_property``: random seeds with hypothesis installed, fixed
   seeds otherwise — never a skip);
2. bitwise verdict equality — every wavefront mode, every layout, every
   format produces the SAME verdict word and work counters as fp32
   (conservative bounds may only add visited nodes; for the aligned
   octree cells the packed coordinates are exact, so the inflation is
   exactly zero — asserted as the ``nodes_visited`` cap).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import seeded_property
from repro.core.counters import (BYTES_META_STREAM, BYTES_META_STREAM_BF16,
                                 BYTES_META_STREAM_U8)
from repro.core.geometry import random_obbs
from repro.core.octree import PAD_CODE, build_octree, device_octree
from repro.core.quantize import (META_FORMAT_WORDS, META_FORMATS, U8_GRID,
                                 bf16_round_down, bf16_round_up, bf16_support,
                                 dequantize_child_aabb_u8, format_eligible,
                                 pack_geom_bf16, pack_topo_bf16, pack_topo_u8,
                                 quantize_aabb_bf16, quantize_child_aabb_u8,
                                 unpack_geom_bf16, unpack_topo)
from repro.engine.executor import CollisionEngine, EngineConfig
from repro.kernels.persist.ops import (MetaChoice, choose_meta_layout,
                                       meta_stream_bytes, meta_table_bytes,
                                       traverse_whole)

WORK_FIELDS = ("nodes_traversed", "leaf_tests", "axis_tests_executed",
               "axis_tests_decoded", "sphere_tests", "frontier_overflow")


def _tree(seed=0, n=3000, depth=4):
    rs = np.random.RandomState(seed)
    pts = (rs.rand(n, 3).astype(np.float32) * 2 - 1)
    return build_octree(pts, depth=depth,
                        scene_lo=np.full(3, -1.0, np.float32), scene_size=2.0)


# ---------------------------------------------------------------------------
# Containment properties (satellite: quantization soundness)
# ---------------------------------------------------------------------------

@seeded_property(max_examples=25)
def test_u8_quantized_bounds_contain_fp32(seed):
    """Outward-rounded u8 child bounds ⊇ fp32 bounds, per parent cell —
    including degenerate thin (zero-extent) child boxes."""
    rs = np.random.RandomState(seed)
    parent_lo = rs.uniform(-10, 10, (64, 3)).astype(np.float32)
    cell = np.float32(rs.uniform(1e-3, 10))
    a = rs.uniform(0, 1, (64, 3))
    b = rs.uniform(0, 1, (64, 3))
    lo01, hi01 = np.minimum(a, b), np.maximum(a, b)
    if seed % 3 == 0:           # degenerate thin boxes: zero extent per axis
        hi01[:, seed % 2] = lo01[:, seed % 2]
    child_lo = parent_lo + lo01 * cell
    child_hi = parent_lo + hi01 * cell
    qlo, qhi = quantize_child_aabb_u8(child_lo, child_hi, parent_lo, cell)
    dlo, dhi = dequantize_child_aabb_u8(qlo, qhi, parent_lo, cell)
    assert (dlo <= child_lo).all()
    assert (dhi >= child_hi).all()
    # offsets live on the parent's 256-grid
    assert qlo.dtype == np.uint8 and qhi.dtype == np.uint8
    assert int(qlo.max()) < U8_GRID and int(qhi.max()) < U8_GRID


@seeded_property(max_examples=25)
def test_bf16_quantized_bounds_contain_fp32(seed):
    """bf16 outward rounding: round_down(lo) <= lo, round_up(hi) >= hi —
    thin boxes (hi == lo) stay contained too."""
    rs = np.random.RandomState(seed)
    lo = rs.uniform(-1e4, 1e4, (256, 3)).astype(np.float32)
    hi = lo + rs.uniform(0, 1e3, (256, 3)).astype(np.float32)
    hi[:32] = lo[:32]                             # degenerate thin boxes
    qlo, qhi = quantize_aabb_bf16(lo, hi)
    assert (qlo <= lo).all()
    assert (qhi >= hi).all()
    # the rounding is tight: one bf16 ulp of slack at most (mantissa step
    # is 2^-7 of the binade, i.e. <= |x| / 128 + smallest normal)
    slack = np.abs(lo) / 128 + 1e-30
    assert (lo - qlo <= slack).all()
    assert (qhi - hi <= np.abs(hi) / 128 + 1e-30).all()


def test_bf16_rounding_matches_ml_dtypes():
    """Cross-check the uint32-truncation bf16 rounding against native
    ml_dtypes casts — skipped WITH A NAMED REASON where the host lacks
    bf16 support (satellite: no raw lowering errors on such hosts)."""
    ok, reason = bf16_support()
    if not ok:
        pytest.skip(reason)
    import ml_dtypes
    rs = np.random.RandomState(11)
    x = np.concatenate([
        rs.uniform(-1e6, 1e6, 512).astype(np.float32),
        np.array([0.0, -0.0, 1.0, -1.0, 2.0 ** -120, -(2.0 ** -120)],
                 np.float32)])
    down, up = bf16_round_down(x), bf16_round_up(x)
    # round_down/up are representable and bracket x ...
    assert (down.astype(ml_dtypes.bfloat16).astype(np.float32) == down).all()
    assert (up.astype(ml_dtypes.bfloat16).astype(np.float32) == up).all()
    assert (down <= x).all() and (up >= x).all()
    # ... and exactly-representable values are fixed points of both.
    rep = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert (bf16_round_down(rep) == rep).all()
    assert (bf16_round_up(rep) == rep).all()


@seeded_property(max_examples=10)
def test_topology_and_geometry_words_round_trip(seed):
    rs = np.random.RandomState(seed)
    n = 128
    full = rs.rand(n) < 0.5
    mask = rs.randint(0, 256, n)
    octant = rs.randint(0, 8, n)
    start_u8 = rs.randint(0, 1 << 20, n)
    start_bf = rs.randint(0, 1 << 23, n)
    f, o, s, m = unpack_topo(pack_topo_u8(full, octant, start_u8, mask), "u8")
    assert (f == full).all() and (o == octant).all()
    assert (s == start_u8).all() and (m == mask).all()
    f, o, s, m = unpack_topo(pack_topo_bf16(full, start_bf, mask), "bf16")
    assert (f == full).all() and (s == start_bf).all() and (m == mask).all()
    level = int(rs.randint(0, 11))
    xyz = rs.randint(0, 1 << level, (n, 3))
    assert (unpack_geom_bf16(pack_geom_bf16(xyz, level), level) == xyz).all()


def test_pack_raises_on_pointer_overflow():
    with pytest.raises(ValueError, match="overflows"):
        pack_topo_u8([0], [0], [1 << 20], [0])
    with pytest.raises(ValueError, match="overflows"):
        pack_topo_bf16([0], [1 << 23], [0])
    with pytest.raises(ValueError, match="leaf grid"):
        pack_geom_bf16(np.array([[4, 0, 0]]), 2)   # coord >= 2**level


# ---------------------------------------------------------------------------
# Packed device tables
# ---------------------------------------------------------------------------

def test_packed_tables_encode_the_fp32_channels():
    tree = _tree(3, 2000, 4)
    devs = {f: device_octree(tree, meta_format=f) for f in META_FORMATS}
    ref = devs["fp32"]
    for f in META_FORMATS:
        assert devs[f].meta_format == f
        assert devs[f].node_meta.shape[-1] == META_FORMAT_WORDS[f]
        # unpacked channel planes are retained identically in every format
        assert (devs[f].codes == ref.codes).all()
        assert (devs[f].child_start == ref.child_start).all()
    codes = np.asarray(ref.codes)
    occ = codes != PAD_CODE
    for f in ("bf16", "u8"):
        w0 = np.asarray(devs[f].node_meta[..., 0])
        full, octant, start, mask = unpack_topo(w0, f)
        assert (full[occ] == np.asarray(ref.full)[occ]).all(), f
        assert (start[occ] == np.asarray(ref.child_start)[occ]).all(), f
        assert (mask[occ] == np.asarray(ref.child_mask)[occ]).all(), f
        # pad rows pack to zero words (PAD_CODE coords would overflow)
        assert (w0[~occ] == 0).all(), f
    assert (unpack_topo(np.asarray(devs["u8"].node_meta[..., 0]),
                        "u8")[1][occ] == (codes & 7)[occ]).all()


# ---------------------------------------------------------------------------
# Bitwise verdict equality + nodes_visited inflation cap (all modes)
# ---------------------------------------------------------------------------

def test_bitwise_verdicts_across_all_wavefront_modes_and_formats():
    """The tentpole soundness sweep: all four wavefront modes, quantized
    verdicts AND work counters bitwise-identical to fp32; nodes_visited
    inflation is exactly 1x (aligned cells quantize exactly)."""
    tree = _tree(0)
    obbs = random_obbs(jax.random.PRNGKey(3), 48)
    base = {}
    for mode in ("wavefront_host", "wavefront", "wavefront_fused",
                 "wavefront_persistent"):
        base[mode] = CollisionEngine(tree, EngineConfig(mode=mode)).query(obbs)
        assert (base[mode][0] == base["wavefront_host"][0]).all(), mode
    ref_v, ref_c = base["wavefront_fused"]
    for mode in ("wavefront_fused", "wavefront_persistent"):
        for fmt in META_FORMATS:
            for stream in (False, True):
                eng = CollisionEngine(tree, EngineConfig(
                    mode=mode, meta_format=fmt, stream_meta=stream))
                assert eng.meta_format == fmt
                v, c = eng.query(obbs)
                ctx = (mode, fmt, stream)
                assert (np.asarray(v) == np.asarray(ref_v)).all(), ctx
                for fld in WORK_FIELDS:
                    assert getattr(c, fld) == getattr(ref_c, fld), (ctx, fld)
                assert c.nodes_per_level == ref_c.nodes_per_level, ctx
                assert (c.exit_histogram == ref_c.exit_histogram).all(), ctx
                # the inflation bound: quantization adds ZERO visits here
                assert c.nodes_traversed == ref_c.nodes_traversed, ctx


def test_streamed_bytes_scale_with_format_width():
    """Row COUNT is format-independent; streamed bytes divide by exactly
    2x (bf16) and 4x (u8) — the ISSUE's >= 3x acceptance mechanism."""
    tree = _tree(1)
    obbs = random_obbs(jax.random.PRNGKey(5), 32)
    rows, bytes_ = {}, {}
    for fmt in META_FORMATS:
        eng = CollisionEngine(tree, EngineConfig(
            mode="wavefront_persistent", meta_format=fmt, stream_meta=True))
        _, c = eng.query(obbs)
        rows[fmt], bytes_[fmt] = c.meta_rows_streamed, c.meta_bytes_streamed
    assert rows["fp32"] > 0
    assert rows["fp32"] == rows["bf16"] == rows["u8"]
    assert bytes_["fp32"] == rows["fp32"] * BYTES_META_STREAM
    assert bytes_["bf16"] == rows["fp32"] * BYTES_META_STREAM_BF16
    assert bytes_["u8"] == rows["fp32"] * BYTES_META_STREAM_U8
    assert bytes_["fp32"] == 4 * bytes_["u8"] == 2 * bytes_["bf16"]


def test_pallas_interpret_kernel_bitwise_across_formats():
    """The megakernel arm (interpret=True) matches the jnp ref on every
    format x layout, stats included — the kernel's in-register dequantize
    and u8 own-code frontier lane against the ref's."""
    tree = _tree(2, 2500, 4)
    obbs = random_obbs(jax.random.PRNGKey(7), 24)
    cap = 4096                       # no overflow: global == tile-local
    ref = traverse_whole(obbs.center, obbs.half, obbs.rot,
                         device_octree(tree), cap,
                         use_spheres=False, use_pallas=False, streamed=False,
                         bq=16)
    assert int(ref[1]["overflow"]) == 0
    for fmt in META_FORMATS:
        dev = device_octree(tree, meta_format=fmt)
        for stream in (False, True):
            pal = traverse_whole(obbs.center, obbs.half, obbs.rot, dev, cap,
                                 use_spheres=False, use_pallas=True,
                                 interpret=True, streamed=stream, bq=16)
            assert bool(jnp.all(ref[0] == pal[0])), (fmt, stream)
            for k in ref[1]:
                # The streamed layout moves only its schedule counters.
                if not stream or k not in ("meta_rows", "meta_gathers"):
                    assert bool(jnp.all(ref[1][k] == pal[1][k])), \
                        (fmt, stream, k)


# ---------------------------------------------------------------------------
# Chooser + EngineConfig + rebind invalidation
# ---------------------------------------------------------------------------

def test_choose_meta_layout_format_rules():
    depth, n_max = 5, 1024
    t32 = meta_table_bytes(depth, n_max, "fp32")
    # widest-first for residency: fp32 stays fp32 when it fits ...
    assert choose_meta_layout(depth, n_max, t32) == MetaChoice("resident",
                                                               "fp32")
    # ... compression is taken only to buy residency back ...
    assert choose_meta_layout(depth, n_max, t32 // 2) == \
        MetaChoice("resident", "bf16")
    assert choose_meta_layout(depth, n_max, t32 // 4) == \
        MetaChoice("resident", "u8")
    # ... and a truly over-budget table streams at the narrowest format.
    assert choose_meta_layout(depth, n_max, t32 // 8) == \
        MetaChoice("streamed", "u8")
    # pinned layouts
    assert choose_meta_layout(depth, n_max, t32 // 8,
                              layout="streamed") == MetaChoice("streamed",
                                                               "u8")
    assert choose_meta_layout(depth, n_max, t32 // 2,
                              layout="resident") == MetaChoice("resident",
                                                               "bf16")
    # pinned formats: layout falls out of that format's own table size
    assert choose_meta_layout(depth, n_max, t32 // 2, fmt="fp32") == \
        MetaChoice("streamed", "fp32")
    assert choose_meta_layout(depth, n_max, t32 // 2, fmt="bf16") == \
        MetaChoice("resident", "bf16")
    # eligibility: u8's 20-bit pointer cannot index a 2**21-row level
    assert not format_eligible("u8", 1 << 21)
    assert format_eligible("bf16", 1 << 21)
    assert format_eligible("fp32", 1 << 30)
    assert choose_meta_layout(depth, 1 << 21, 0).fmt == "bf16"
    with pytest.raises(ValueError, match="child_start"):
        choose_meta_layout(depth, 1 << 21, 0, fmt="u8")
    with pytest.raises(ValueError, match="unknown meta_format"):
        choose_meta_layout(depth, n_max, fmt="f16")
    # default-arg identities: fp32 pricing is unchanged from PR 5
    assert meta_table_bytes(depth, n_max) == meta_table_bytes(depth, n_max,
                                                              "fp32")
    assert meta_stream_bytes(n_max) == meta_stream_bytes(n_max, "fp32")


def test_engine_config_meta_format_validation():
    with pytest.raises(ValueError, match="unknown meta_format"):
        EngineConfig(mode="wavefront_persistent", meta_format="int4")
    with pytest.raises(ValueError, match="CSR mode"):
        EngineConfig(mode="wavefront", meta_format="u8")
    cfg = EngineConfig(mode="wavefront_persistent", meta_format="u8")
    assert cfg.meta_format == "u8"


def test_rebind_reruns_chooser_across_size_boundary():
    """Satellite: rebind_octrees must re-run the layout/format chooser.
    A scene grown past the residency boundary flips the SAME engine from
    resident-fp32 to a streamed compressed format, and the rebound
    verdicts match a fresh engine's."""
    small, big = _tree(4, 600, 4), _tree(5, 20000, 5)
    n_small = max(len(lv.codes) for lv in small.levels)
    budget = meta_table_bytes(small.depth, n_small)     # small fits exactly
    eng = CollisionEngine(small, EngineConfig(
        mode="wavefront_persistent", vmem_budget=budget))
    assert (eng.meta_layout, eng.meta_format) == ("resident", "fp32")
    obbs = random_obbs(jax.random.PRNGKey(1), 16)
    eng.query(obbs)
    eng.rebind_octrees(big)
    choice = choose_meta_layout(
        big.depth, max(len(lv.codes) for lv in big.levels), budget)
    # the stale small-scene decision must NOT survive the rebind
    assert (eng.meta_layout, eng.meta_format) == tuple(choice)
    assert (eng.meta_layout, eng.meta_format) != ("resident", "fp32")
    v, c = eng.query(obbs)
    fresh_v, fresh_c = CollisionEngine(big, EngineConfig(
        mode="wavefront_persistent", vmem_budget=budget)).query(obbs)
    assert (np.asarray(v) == np.asarray(fresh_v)).all()
    assert c.nodes_traversed == fresh_c.nodes_traversed
    assert c.meta_bytes_streamed == fresh_c.meta_bytes_streamed
    # ... and the device-table cache was invalidated with it: the packed
    # table the engine now serves is the big scene's, in the new format.
    assert eng.device_tree.meta_format == choice.fmt
