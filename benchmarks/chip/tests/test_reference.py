"""The plain reference: its SAT, its voxel grid, and its agreement with
the program at a small size."""
import chipbench_paths  # noqa: F401  (first: the import path)
import numpy as np
import pytest

import fk
import compare
import reference
import scenes
from repro.core.geometry import OBBs
from repro.core.octree import build_octree, morton_decode
from repro.engine import CollisionEngine, EngineConfig, plan_queries

CUBBY = [[[0.8, -0.5, 0.0], [0.82, 0.54, 0.98]],
         [[0.45, -0.5, 0.0], [0.8, 0.54, 0.02]],
         [[0.45, -0.5, 0.32], [0.8, 0.54, 0.34]],
         [[0.45, -0.5, 0.0], [0.8, -0.48, 0.98]],
         [[0.45, 0.18, 0.0], [0.8, 0.2, 0.98]]]


def _rot_z(a):
    c, s = np.cos(a), np.sin(a)
    return np.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 1.0]])


def test_sat_gap_axis_aligned_and_rotated():
    h = np.full(3, 0.1)
    cube = np.zeros((1, 3))
    sep = reference.sat_separation([0.5, 0, 0], h, np.eye(3), cube, 0.2)
    assert sep[0] == pytest.approx(0.2)
    sep = reference.sat_separation([0.5, 0, 0], h, _rot_z(np.pi / 4), cube,
                                   0.2)
    assert sep[0] == pytest.approx(0.5 - 0.2 - 0.1 * np.sqrt(2))
    sep = reference.sat_separation([0.25, 0.1, 0], h, _rot_z(0.3), cube, 0.2)
    assert sep[0] < 0


def test_voxels_are_the_program_octree_leaves():
    pts = scenes.surface_points(CUBBY, 20000, np.random.default_rng(0))
    tree = build_octree(pts, depth=5)
    vox = reference.VoxelScene(pts, 5)
    leaves = np.stack(morton_decode(tree.levels[5].codes), -1)
    grid = np.zeros_like(vox.grid)
    grid[leaves[:, 0], leaves[:, 1], leaves[:, 2]] = True
    np.testing.assert_array_equal(vox.grid, grid)
    assert int(vox.grid.sum()) == tree.num_leaves


def test_reference_agrees_with_program():
    rng = np.random.default_rng(3)
    pts = scenes.surface_points(CUBBY, 20000, rng)
    tree = build_octree(pts, depth=5)
    start, end = fk.segments(rng, 6, {"kind": "uniform"})
    c, h, r = fk.link_obbs(fk.waypoints(start, end, 20))
    got, _ = CollisionEngine(
        tree, EngineConfig(mode="wavefront_persistent")).execute(
            plan_queries(OBBs(center=c, half=h, rot=r)))
    sep = reference.VoxelScene(pts, 5).separation(c, h, r)
    assert 0 < (sep <= 0).sum() < len(sep)
    ok, checks = compare.judge(dict(compare.readings(got, sep),
                                    unanswered=0))
    assert ok, checks
