"""Scene point clouds from a configuration's obstacle boxes.

A configuration file lists its obstacles as axis-aligned boxes
(``"boxes": [[lo_xyz, hi_xyz], ...]``) and a point count.  The cloud is
``num_points`` points drawn from the seed, uniform over the union of the
box surfaces: a box by surface area, one of its six faces by area, then a
uniform point on that face.  That is what a depth camera's fused cloud of
the same boxes looks like, and what the program's own scene builder
(``repro.data.robotics.make_scene``) draws for its environment families.
"""
from __future__ import annotations

import numpy as np


def surface_points(boxes, num_points: int, rng: np.random.Generator
                   ) -> np.ndarray:
    """(num_points, 3) float32 points on the faces of ``boxes``."""
    b = np.asarray(boxes, np.float64)
    lo, hi = b[:, 0], b[:, 1]
    size = hi - lo
    # Face pair k is normal to axis k; its area is the product of the two
    # other sides.  Each box has two faces per axis.
    pair_area = np.stack([size[:, 1] * size[:, 2], size[:, 0] * size[:, 2],
                          size[:, 0] * size[:, 1]], -1)
    face_area = np.repeat(pair_area, 2, axis=1).reshape(-1)   # (B*6,)
    face = rng.choice(face_area.size, size=num_points,
                      p=face_area / face_area.sum())
    box, axis, side = face // 6, (face % 6) // 2, face % 2
    pts = lo[box] + rng.uniform(size=(num_points, 3)) * size[box]
    rows = np.arange(num_points)
    pts[rows, axis] = np.where(side == 1, hi[box, axis], lo[box, axis])
    return pts.astype(np.float32)
