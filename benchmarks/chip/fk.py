"""Panda-like arm forward kinematics in numpy, and the joint-space samplers.

The benchmark makes every query OBB on the host with this module, so the
OBBs of a seed are the same on every backend: JAX's transcendental
functions differ between the CPU and the TPU in the last bits, which moved
link poses enough to change 4 of 10,500 verdicts of one batch.  The chain,
link boxes and joint limits are those of the program's
``repro.core.geometry.arm_link_obbs`` and ``repro.data.robotics``;
``tests/test_fk.py`` holds the two to float32 rounding of each other.

FK runs in float64 and rounds once to float32, the engine's pool dtype.
"""
from __future__ import annotations

import numpy as np

NUM_LINKS = 7

# Modified DH parameters (a, d, alpha) per joint.
PANDA_DH = np.array([
    [0.0000, 0.3330, 0.0],
    [0.0000, 0.0000, -np.pi / 2],
    [0.0000, 0.3160, np.pi / 2],
    [0.0825, 0.0000, np.pi / 2],
    [-0.0825, 0.3840, -np.pi / 2],
    [0.0000, 0.0000, np.pi / 2],
    [0.0880, 0.0000, np.pi / 2],
])

# Per-link box half extents and box centre in the link frame (metres).
LINK_HALF = np.array([
    [0.060, 0.060, 0.170],
    [0.060, 0.090, 0.060],
    [0.060, 0.060, 0.160],
    [0.060, 0.085, 0.060],
    [0.055, 0.055, 0.195],
    [0.060, 0.080, 0.055],
    [0.050, 0.050, 0.080],
])
LINK_OFF = np.array([
    [0.0, 0.0, -0.170],
    [0.0, 0.0, 0.0],
    [0.0, 0.0, -0.160],
    [0.0825, 0.0, 0.0],
    [-0.0825, 0.0, -0.190],
    [0.0, 0.0, 0.0],
    [0.088, 0.0, 0.080],
])

JOINT_LO = np.array([-2.8, -1.7, -2.8, -3.0, -2.8, 0.0, -2.8])
JOINT_HI = np.array([2.8, 1.7, 2.8, -0.1, 2.8, 3.7, 2.8])


def link_obbs(q: np.ndarray, base=(0.0, 0.0, 0.0)):
    """Joint angles (N, 7) -> link OBBs ``(center (N*7, 3), half (N*7, 3),
    rot (N*7, 3, 3))`` float32, waypoint-major then link."""
    q = np.asarray(q, np.float64).reshape(-1, NUM_LINKS)
    n = q.shape[0]
    T = np.broadcast_to(np.eye(4), (n, 4, 4)).copy()
    T[:, :3, 3] = base
    centers = np.empty((n, NUM_LINKS, 3))
    rots = np.empty((n, NUM_LINKS, 3, 3))
    for j in range(NUM_LINKS):
        a, d, alpha = PANDA_DH[j]
        ct, st = np.cos(q[:, j]), np.sin(q[:, j])
        ca, sa = np.cos(alpha), np.sin(alpha)
        Tj = np.zeros((n, 4, 4))
        Tj[:, 0] = np.stack([ct, -st, np.zeros(n), np.full(n, a)], -1)
        Tj[:, 1] = np.stack([st * ca, ct * ca, np.full(n, -sa),
                             np.full(n, -d * sa)], -1)
        Tj[:, 2] = np.stack([st * sa, ct * sa, np.full(n, ca),
                             np.full(n, d * ca)], -1)
        Tj[:, 3, 3] = 1.0
        T = T @ Tj
        rots[:, j] = T[:, :3, :3]
        centers[:, j] = T[:, :3, 3] + T[:, :3, :3] @ LINK_OFF[j]
    half = np.broadcast_to(LINK_HALF, (n, NUM_LINKS, 3))
    return (centers.reshape(-1, 3).astype(np.float32),
            np.ascontiguousarray(half.reshape(-1, 3), np.float32),
            rots.reshape(-1, 3, 3).astype(np.float32))


def segments(rng: np.random.Generator, n: int, goal: dict) -> tuple:
    """``n`` joint-space segments: start uniform in the joint limits, goal
    either uniform too (``{"kind": "uniform"}``) or the start plus a
    per-joint uniform offset clipped to the limits
    (``{"kind": "offset", "rad": r}``).  Returns (start, goal), (n, 7)."""
    start = rng.uniform(JOINT_LO, JOINT_HI, (n, NUM_LINKS))
    if goal["kind"] == "uniform":
        end = rng.uniform(JOINT_LO, JOINT_HI, (n, NUM_LINKS))
    elif goal["kind"] == "offset":
        r = float(goal["rad"])
        end = np.clip(start + rng.uniform(-r, r, (n, NUM_LINKS)),
                      JOINT_LO, JOINT_HI)
    else:
        raise ValueError(f"unknown goal kind {goal['kind']!r}")
    return start, end


def waypoints(start: np.ndarray, end: np.ndarray, n: int) -> np.ndarray:
    """Straight joint-space paths, ``n`` waypoints each, endpoints included:
    (S, 7) x 2 -> (S, n, 7)."""
    t = np.linspace(0.0, 1.0, n)[None, :, None]
    return (1.0 - t) * start[:, None, :] + t * end[:, None, :]
