"""Geometry primitives for the collision engine.

Struct-of-arrays layouts throughout (TPU-friendly): a *batch* of OBBs is
(centers (M,3), half_extents (M,3), rot (M,3,3)); a batch of AABBs is
(centers (N,3), half_extents (N,3)).  ``rot[m]`` columns are the OBB's local
axes expressed in world coordinates, so ``world = rot @ local + center``.

Also provides a minimal 7-DOF serial arm (Franka-like DH chain) whose links
carry fixed local OBBs, used to turn joint-space trajectories into the OBB
sets the paper collision-checks (Table III).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class OBBs:
    """Batch of oriented bounding boxes (SoA)."""

    center: jax.Array  # (M, 3)
    half: jax.Array    # (M, 3)
    rot: jax.Array     # (M, 3, 3), columns = local axes in world frame

    def tree_flatten(self):
        return (self.center, self.half, self.rot), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n(self) -> int:
        return self.center.shape[0]

    def bounding_sphere_radius(self) -> jax.Array:
        """Radius of the sphere that encloses each OBB (||half||)."""
        return jnp.linalg.norm(self.half, axis=-1)

    def inscribed_sphere_radius(self) -> jax.Array:
        """Radius of the largest sphere inside each OBB (min(half))."""
        return jnp.min(self.half, axis=-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AABBs:
    """Batch of axis-aligned bounding boxes (SoA)."""

    center: jax.Array  # (N, 3)
    half: jax.Array    # (N, 3)

    def tree_flatten(self):
        return (self.center, self.half), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n(self) -> int:
        return self.center.shape[0]

    @property
    def lo(self) -> jax.Array:
        return self.center - self.half

    @property
    def hi(self) -> jax.Array:
        return self.center + self.half


def rotation_from_euler(rpy: jax.Array) -> jax.Array:
    """Rotation matrices from (…, 3) roll/pitch/yaw angles -> (…, 3, 3)."""
    r, p, y = rpy[..., 0], rpy[..., 1], rpy[..., 2]
    cr, sr = jnp.cos(r), jnp.sin(r)
    cp, sp = jnp.cos(p), jnp.sin(p)
    cy, sy = jnp.cos(y), jnp.sin(y)
    row0 = jnp.stack([cy * cp, cy * sp * sr - sy * cr, cy * sp * cr + sy * sr], -1)
    row1 = jnp.stack([sy * cp, sy * sp * sr + cy * cr, sy * sp * cr - cy * sr], -1)
    row2 = jnp.stack([-sp, cp * sr, cp * cr], -1)
    return jnp.stack([row0, row1, row2], -2)


def point_aabb_sq_distance(points: jax.Array, aabb_center: jax.Array,
                           aabb_half: jax.Array) -> jax.Array:
    """Squared distance from points (...,3) to AABBs (...,3)/(...,3), broadcast."""
    d = jnp.abs(points - aabb_center) - aabb_half
    return jnp.sum(jnp.square(jnp.maximum(d, 0.0)), axis=-1)


# ---------------------------------------------------------------------------
# Serial arm forward kinematics (Franka-Emika-Panda-like DH chain).
# ---------------------------------------------------------------------------

# Modified DH parameters (a, d, alpha) per joint; 7 revolute joints.
_PANDA_DH = np.array(
    [
        # a      d       alpha
        [0.0000, 0.3330, 0.0],
        [0.0000, 0.0000, -np.pi / 2],
        [0.0000, 0.3160, np.pi / 2],
        [0.0825, 0.0000, np.pi / 2],
        [-0.0825, 0.3840, -np.pi / 2],
        [0.0000, 0.0000, np.pi / 2],
        [0.0880, 0.0000, np.pi / 2],
    ],
    dtype=np.float32,
)

# Per-link local OBB half-extents (rough Panda link volumes, metres).
PANDA_LINK_HALF = np.array(
    [
        [0.060, 0.060, 0.170],
        [0.060, 0.090, 0.060],
        [0.060, 0.060, 0.160],
        [0.060, 0.085, 0.060],
        [0.055, 0.055, 0.195],
        [0.060, 0.080, 0.055],
        [0.050, 0.050, 0.080],
    ],
    dtype=np.float32,
)

# Local OBB centre offset (in the link frame) so boxes sit mid-link.
_PANDA_LINK_OFF = np.array(
    [
        [0.0, 0.0, -0.170],
        [0.0, 0.0, 0.0],
        [0.0, 0.0, -0.160],
        [0.0825, 0.0, 0.0],
        [-0.0825, 0.0, -0.190],
        [0.0, 0.0, 0.0],
        [0.088, 0.0, 0.080],
    ],
    dtype=np.float32,
)

NUM_LINKS = 7

#: cos and sin of each joint's twist, float32, computed once on the host.
_DH_COS = np.cos(_PANDA_DH[:, 2]).astype(np.float32)
_DH_SIN = np.sin(_PANDA_DH[:, 2]).astype(np.float32)


def _dh_transform(theta: jax.Array, a, d, ca, sa) -> jax.Array:
    """Modified-DH 4x4 transform for one joint; theta (...,) -> (...,4,4)."""
    ct, st = jnp.cos(theta), jnp.sin(theta)
    zeros = jnp.zeros_like(ct)
    ones = jnp.ones_like(ct)
    rows = [
        jnp.stack([ct, -st, zeros, a * ones], -1),
        jnp.stack([st * ca, ct * ca, -sa * ones, -d * sa * ones], -1),
        jnp.stack([st * sa, ct * sa, ca * ones, d * ca * ones], -1),
        jnp.stack([zeros, zeros, zeros, ones], -1),
    ]
    return jnp.stack(rows, -2)


def _compose(T: jax.Array, U: jax.Array) -> jax.Array:
    """``T @ U`` of (B, 4, 4) transforms as float32 products summed in a
    fixed order: no matmul unit, whose default precision on a TPU rounds
    to bfloat16, and the same bits in every program that calls it."""
    return (T[:, :, 0:1] * U[:, 0:1, :] + T[:, :, 1:2] * U[:, 1:2, :]
            + T[:, :, 2:3] * U[:, 2:3, :] + T[:, :, 3:4] * U[:, 3:4, :])


@jax.jit
def arm_link_obbs(joint_angles: jax.Array,
                  base_pos: jax.Array | None = None) -> OBBs:
    """Forward kinematics: joint angles (..., 7) -> per-link world OBBs.

    Returns OBBs with leading dims flattened: (prod(...)*7,) boxes.
    Elementwise float32 throughout (see :func:`_compose`), compiled as one
    program: op by op it takes ~20 ms of host dispatch a call.
    """
    q = jnp.asarray(joint_angles, jnp.float32).reshape((-1, NUM_LINKS))
    B = q.shape[0]
    base = jnp.eye(4, dtype=jnp.float32)
    if base_pos is not None:
        base = base.at[:3, 3].set(jnp.asarray(base_pos, jnp.float32))
    T = jnp.broadcast_to(base, (B, 4, 4))
    centers, rots = [], []
    for j in range(NUM_LINKS):
        a, d = _PANDA_DH[j, 0], _PANDA_DH[j, 1]
        T = _compose(T, _dh_transform(q[:, j], a, d, _DH_COS[j], _DH_SIN[j]))
        R = T[:, :3, :3]
        off = _PANDA_LINK_OFF[j]
        c = T[:, :3, 3] + (R[:, :, 0] * off[0] + R[:, :, 1] * off[1]
                           + R[:, :, 2] * off[2])
        centers.append(c)
        rots.append(R)
    center = jnp.stack(centers, 1).reshape((-1, 3))          # (B*7, 3)
    rot = jnp.stack(rots, 1).reshape((-1, 3, 3))             # (B*7, 3, 3)
    half = jnp.tile(jnp.asarray(PANDA_LINK_HALF), (B, 1))   # (B*7, 3)
    return OBBs(center=center, half=half, rot=rot)


def trajectory_obbs(start: jax.Array, goal: jax.Array, num_waypoints: int,
                    base_pos: jax.Array | None = None) -> OBBs:
    """Discretize a straight joint-space path into waypoints and emit OBBs."""
    t = jnp.linspace(0.0, 1.0, num_waypoints)[:, None]
    qs = (1.0 - t) * start[None, :] + t * goal[None, :]
    return arm_link_obbs(qs, base_pos=base_pos)


def random_obbs(key: jax.Array, n: int, scene_lo: float = -1.0,
                scene_hi: float = 1.0, min_half: float = 0.02,
                max_half: float = 0.25) -> OBBs:
    """Random OBBs for testing."""
    k1, k2, k3 = jax.random.split(key, 3)
    center = jax.random.uniform(k1, (n, 3), minval=scene_lo, maxval=scene_hi)
    half = jax.random.uniform(k2, (n, 3), minval=min_half, maxval=max_half)
    rot = rotation_from_euler(
        jax.random.uniform(k3, (n, 3), minval=-np.pi, maxval=np.pi))
    return OBBs(center=center, half=half, rot=rot)


def random_aabbs(key: jax.Array, n: int, scene_lo: float = -1.0,
                 scene_hi: float = 1.0, min_half: float = 0.02,
                 max_half: float = 0.25) -> AABBs:
    k1, k2 = jax.random.split(key)
    center = jax.random.uniform(k1, (n, 3), minval=scene_lo, maxval=scene_hi)
    half = jax.random.uniform(k2, (n, 3), minval=min_half, maxval=max_half)
    return AABBs(center=center, half=half)


def obb_corners(obbs: OBBs) -> jax.Array:
    """All 8 world-space corners of each OBB -> (M, 8, 3)."""
    signs = jnp.asarray(
        [[sx, sy, sz] for sx in (-1, 1) for sy in (-1, 1) for sz in (-1, 1)],
        jnp.float32)                                           # (8, 3)
    local = signs[None, :, :] * obbs.half[:, None, :]          # (M, 8, 3)
    return obbs.center[:, None, :] + jnp.einsum("mij,mkj->mki", obbs.rot, local)
