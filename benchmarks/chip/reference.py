"""Plain reference of the collision verdict, independent of the program.

Semantics (both configurations): a scene is its point cloud voxelized on
the ``2**depth`` grid over the cloud's padded bounding cube; a link OBB
collides iff it intersects the union of the occupied voxels.  The program
answers that with an octree walk and a staged float32 separating-axis test
(SAT); the reference answers it here with a dense occupancy grid and a
float64 SAT of the OBB against every occupied voxel near it, and imports
nothing of the program.

The reference gives more than a verdict: for each OBB the *separation*
``min over voxels of max over the 15 SAT axes of the projected gap``,
every axis normalised, so the number is in metres.  It is > 0 for a free
OBB (and then a lower bound on its distance to the scene) and <= 0 for a
colliding one (its magnitude the smallest push that separates it along a
SAT axis).  Voxels farther than ``reach`` from the OBB's bounding box are
not visited, so separations are clipped to ``reach``.

The same code computed in bfloat16 (``dtype=ml_dtypes.bfloat16``) is the
benchmark's control: the precision below the float32 the program states.
"""
from __future__ import annotations

import numpy as np

#: Separations are clipped here (metres): wider than any limit compared.
REACH = 0.01


class VoxelScene:
    """Occupancy grid of a point cloud at ``depth``, on the program's cube.

    The cube is the one the program's octree builder draws round the
    cloud: the float32 bounding box grown by a thousandth of its largest
    side, made cubic.  The expressions below are written as that builder
    writes them, in the same dtypes, so every point lands in the same cell.
    """

    def __init__(self, points: np.ndarray, depth: int):
        points = np.asarray(points, np.float32)
        lo = points.min(0)
        hi = points.max(0)
        pad = 1e-3 * float(np.max(hi - lo) + 1e-6)
        scene_lo = lo - pad
        scene_size = float(np.max(hi - lo) + 2 * pad)
        res = 1 << depth
        rel = (points - scene_lo[None, :]) / scene_size
        cells = np.clip((rel * res).astype(np.int64), 0, res - 1)
        self.res = res
        self.lo = scene_lo.astype(np.float64)
        self.cell = scene_size / res
        self.grid = np.zeros((res, res, res), bool)
        self.grid[cells[:, 0], cells[:, 1], cells[:, 2]] = True
        # Summed-volume table: occupied voxels in any index box in O(1).
        s = np.zeros((res + 1,) * 3, np.int64)
        s[1:, 1:, 1:] = self.grid.cumsum(0).cumsum(1).cumsum(2)
        self._sum = s

    def _count(self, i0, i1):
        """Occupied voxels in index boxes [i0, i1] (inclusive), (N, 3)."""
        s = self._sum
        a, b = i0, i1 + 1
        return (s[b[:, 0], b[:, 1], b[:, 2]] - s[a[:, 0], b[:, 1], b[:, 2]]
                - s[b[:, 0], a[:, 1], b[:, 2]] - s[b[:, 0], b[:, 1], a[:, 2]]
                + s[a[:, 0], a[:, 1], b[:, 2]] + s[a[:, 0], b[:, 1], a[:, 2]]
                + s[b[:, 0], a[:, 1], a[:, 2]] - s[a[:, 0], a[:, 1], a[:, 2]])

    def _boxes(self, c, h, r, reach: float):
        """Index boxes of the voxels within ``reach`` of each OBB's world
        bounding box, and whether the box meets the grid at all."""
        ext = (np.abs(r) @ h[:, :, None])[:, :, 0] + reach
        i0 = np.floor((c - ext - self.lo) / self.cell).astype(np.int64)
        i1 = np.floor((c + ext - self.lo) / self.cell).astype(np.int64)
        inside = (i1 >= 0).all(1) & (i0 < self.res).all(1)
        return (np.clip(i0, 0, self.res - 1), np.clip(i1, 0, self.res - 1),
                inside)

    def near_voxels(self, center, half, rot) -> np.ndarray:
        """Occupied voxels in each OBB's world bounding box: a cheap gauge
        of the traversal work an OBB asks for."""
        c, h, r = (np.asarray(x, np.float64) for x in (center, half, rot))
        i0, i1, inside = self._boxes(c, h, r, 0.0)
        return np.where(inside, self._count(i0, i1), 0)

    def separation(self, center, half, rot, reach: float = REACH,
                   dtype=np.float64) -> np.ndarray:
        """Per-OBB separation (metres, float64 out), clipped to ``reach``.

        ``dtype`` is the arithmetic of the SAT itself (inputs are rounded
        to it first); the voxel search is exact either way.
        """
        c, h, r = (np.asarray(x, np.float64) for x in (center, half, rot))
        i0, i1, inside = self._boxes(c, h, r, reach)
        out = np.full(len(c), reach)
        near = np.flatnonzero(inside & (self._count(i0, i1) > 0))
        for n in near:
            (a0, b0, c0), (a1, b1, c1) = i0[n], i1[n]
            idx = np.argwhere(self.grid[a0:a1 + 1, b0:b1 + 1, c0:c1 + 1])
            idx += i0[n]
            centers = self.lo + (idx + 0.5) * self.cell
            sep = sat_separation(c[n], h[n], r[n], centers, self.cell / 2,
                                 dtype)
            out[n] = min(reach, float(sep.min()))
        return out


def sat_separation(c, h, R, box_c, a, dtype=np.float64) -> np.ndarray:
    """SAT separation of one OBB (centre c, half h, rotation R whose
    columns are its axes) against K axis-aligned cubes (centres box_c
    (K, 3), half side a): the largest of the 15 normalised axis gaps."""
    one = np.asarray(1, dtype)
    c, h, R = (np.asarray(x, dtype) for x in (c, h, R))
    a = np.asarray(a, dtype)
    t = np.asarray(box_c, dtype)
    t = [c[k] - t[:, k] for k in range(3)]                 # (K,) each
    absR = np.abs(R)
    gaps = []
    for i in range(3):                                      # world axes
        rb = h[0] * absR[i, 0] + h[1] * absR[i, 1] + h[2] * absR[i, 2]
        gaps.append(np.abs(t[i]) - (a + rb))
    for j in range(3):                                      # OBB axes
        tl = t[0] * R[0, j] + t[1] * R[1, j] + t[2] * R[2, j]
        ra = a * (absR[0, j] + absR[1, j] + absR[2, j])
        gaps.append(np.abs(tl) - (ra + h[j]))
    for i in range(3):                                      # e_i x R_j
        i1, i2 = (i + 1) % 3, (i + 2) % 3
        for j in range(3):
            norm = np.sqrt(np.maximum(one - R[i, j] * R[i, j],
                                      np.asarray(0, dtype)))
            if float(norm) < 1e-6:
                continue          # parallel edges: a face axis decides
            j1, j2 = (j + 1) % 3, (j + 2) % 3
            ra = a * (absR[i2, j] + absR[i1, j])
            rb = h[j1] * absR[i, j2] + h[j2] * absR[i, j1]
            tl = t[i2] * R[i1, j] - t[i1] * R[i2, j]
            gaps.append((np.abs(tl) - (ra + rb)) / norm)
    g = gaps[0]
    for x in gaps[1:]:
        g = np.maximum(g, x)
    return g.astype(np.float64)
