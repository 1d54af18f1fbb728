"""MPiNets (Motion Policy Networks; Fishman et al., CoRL 2022): a neural
motion policy over a segmented point cloud, rolled out step by step.

Each step sees 6,272 points at the published widths: robot points placed
on the arm at the current joints, obstacle points and target points, each
with a segment label (robot 0, obstacle 1, target 2).  The PointNet++
encoder (:mod:`repro.models.pointnet`) turns the cloud into a 2,048-wide
encoding, a joint encoder turns the normalised joints into 64, and a
decoder maps both to a joint step ``delta``::

    q_{t+1} = clamp(q_t + delta(cloud(q_t), q_t), -1, 1)

with joints normalised to [-1, 1] by the joint limits.  The robot's
points move with it, so every step re-places them by forward kinematics
and re-encodes the whole cloud; the target's points stay fixed.

The robot and target points are fixed samples on the link boxes of
:mod:`repro.core.geometry` (MPiNets samples the Panda's meshes), drawn
with the weights from the model's seed.  One :class:`MpiNetsConfig` holds
every width; :data:`MPINETS` is the published one and :data:`SMALL` a
narrow one for CPU tests.  :func:`rollout` plans B problems in one jitted
``lax.scan``; ``repro.core.pipeline.plan_with_collision_gate`` gates it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.geometry import NUM_LINKS, PANDA_LINK_HALF, arm_link_obbs
from repro.data.robotics import PANDA_JOINT_HI, PANDA_JOINT_LO
from repro.models.pointnet import (PointNetConfig, SetAbstraction, dense,
                                   encode, init_pointnet, leaky_relu,
                                   mlp_init)

#: Segment labels of the three point sets.
ROBOT, OBSTACLE, TARGET = 0.0, 1.0, 2.0
#: Matmul precisions a configuration may state: float32-accurate, or the
#: chip's default (one bfloat16 pass on a TPU), the benchmark's control.
PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}


@dataclasses.dataclass(frozen=True)
class MpiNetsConfig:
    robot_points: int
    obstacle_points: int
    target_points: int
    encoder: PointNetConfig
    joint_widths: Tuple[int, ...]
    decoder_widths: Tuple[int, ...]
    steps: int
    precision: str = "highest"     # of every matmul: see PRECISIONS

    @property
    def points(self) -> int:
        return self.robot_points + self.obstacle_points + self.target_points

    @property
    def fps_picks(self) -> int:
        """Dependent FPS picks of one rollout (the first pick is free)."""
        e = self.encoder
        return self.steps * (e.sa1.centres - 1 + e.sa2.centres - 1)


MPINETS = MpiNetsConfig(
    robot_points=2048, obstacle_points=4096, target_points=128,
    encoder=PointNetConfig(
        sa1=SetAbstraction(512, 0.05, 128, (64, 64, 64)),
        sa2=SetAbstraction(128, 0.3, 128, (128, 128, 256)),
        global_widths=(512, 512, 1024),
        head_widths=(4096, 2048, 2048)),
    joint_widths=(32, 64, 128, 128, 64),
    decoder_widths=(512, 256, 128, NUM_LINKS),
    steps=69)

SMALL = MpiNetsConfig(
    robot_points=96, obstacle_points=128, target_points=32,
    encoder=PointNetConfig(
        sa1=SetAbstraction(32, 0.2, 16, (16, 16, 16)),
        sa2=SetAbstraction(8, 0.6, 16, (32, 32, 64)),
        global_widths=(64, 64, 128),
        head_widths=(256, 128, 128)),
    joint_widths=(8, 16, 32, 32, 16),
    decoder_widths=(64, 32, 16, NUM_LINKS),
    steps=4)


def config_from_dict(d: Dict) -> MpiNetsConfig:
    """An :class:`MpiNetsConfig` from its plain-dict form (JSON)."""
    enc = dict(d["encoder"])
    for k in ("sa1", "sa2"):
        sa = dict(enc[k])
        sa["widths"] = tuple(sa["widths"])
        enc[k] = SetAbstraction(**sa)
    for k in ("global_widths", "head_widths"):
        enc[k] = tuple(enc[k])
    d = dict(d, encoder=PointNetConfig(**enc))
    for k in ("joint_widths", "decoder_widths"):
        d[k] = tuple(d[k])
    return MpiNetsConfig(**d)


# ---------------------------------------------------------------------------
# Joints, and the points that stand for the robot and the target.
# ---------------------------------------------------------------------------

def normalize(q_rad: jax.Array) -> jax.Array:
    lo, hi = jnp.asarray(PANDA_JOINT_LO), jnp.asarray(PANDA_JOINT_HI)
    return (q_rad - lo) / (hi - lo) * 2.0 - 1.0


def unnormalize(q: jax.Array) -> jax.Array:
    lo, hi = jnp.asarray(PANDA_JOINT_LO), jnp.asarray(PANDA_JOINT_HI)
    return (q + 1.0) / 2.0 * (hi - lo) + lo


def box_surface(rng: np.random.Generator, half: np.ndarray, n: int
                ) -> np.ndarray:
    """``n`` points uniform on the surface of a box of half extents
    ``half``, in its own frame: (n, 3) float32."""
    h = np.asarray(half, np.float64)
    area = np.repeat([h[1] * h[2], h[0] * h[2], h[0] * h[1]], 2)
    face = rng.choice(6, size=n, p=area / area.sum())
    pts = rng.uniform(-1.0, 1.0, (n, 3)) * h
    axis, side = face // 2, np.where(face % 2 == 1, 1.0, -1.0)
    pts[np.arange(n), axis] = side * h[axis]
    return pts.astype(np.float32)


def link_samples(rng: np.random.Generator, n: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` points split over the link boxes by surface area (largest
    remainders), each uniform on its box: (local (n, 3), link (n,))."""
    h = PANDA_LINK_HALF.astype(np.float64)
    area = h[:, 0] * h[:, 1] + h[:, 0] * h[:, 2] + h[:, 1] * h[:, 2]
    share = n * area / area.sum()
    count = np.floor(share).astype(int)
    count[np.argsort(count - share)[:n - count.sum()]] += 1
    local = np.concatenate([box_surface(rng, PANDA_LINK_HALF[i], c)
                            for i, c in enumerate(count)])
    link = np.repeat(np.arange(NUM_LINKS, dtype=np.int32), count)
    return local, link


def place(local: jax.Array, link: jax.Array, q_rad: jax.Array) -> jax.Array:
    """Link-frame points onto the arm at ``q_rad`` (B, 7): (B, P, 3), as
    ``centre + R @ local`` of each point's link box, in float32."""
    obbs = arm_link_obbs(q_rad)
    B = q_rad.shape[0]
    c = obbs.center.reshape(B, NUM_LINKS, 3)[:, link]          # (B, P, 3)
    r = obbs.rot.reshape(B, NUM_LINKS, 3, 3)[:, link]          # (B, P, 3, 3)
    return c + (r[..., 0] * local[:, 0:1] + r[..., 1] * local[:, 1:2]
                + r[..., 2] * local[:, 2:3])


# ---------------------------------------------------------------------------
# The model.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MpiNets:
    """One policy: its configuration, weights and point samples."""

    cfg: MpiNetsConfig
    params: Dict
    robot_local: jax.Array      # (robot_points, 3) in the link box frame
    robot_link: jax.Array       # (robot_points,) int32
    target_local: jax.Array     # (target_points, 3) on the last link's box

    @property
    def num_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))


jax.tree_util.register_dataclass(
    MpiNets, data_fields=["params", "robot_local", "robot_link",
                          "target_local"], meta_fields=["cfg"])


def init_mpinets(cfg: MpiNetsConfig = MPINETS, seed: int = 0) -> MpiNets:
    """Weights with PyTorch's default init and the point samples, all
    drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    params = {"pointnet": init_pointnet(cfg.encoder, rng)}
    params["joints"] = mlp_init(rng, NUM_LINKS, cfg.joint_widths)
    params["decoder"] = mlp_init(
        rng, cfg.encoder.head_widths[-1] + cfg.joint_widths[-1],
        cfg.decoder_widths)
    local, link = link_samples(rng, cfg.robot_points)
    target = box_surface(rng, PANDA_LINK_HALF[-1], cfg.target_points)
    return MpiNets(cfg, params, jnp.asarray(local), jnp.asarray(link),
                   jnp.asarray(target))


def _mlp_leaky(layers, x, precision) -> jax.Array:
    for layer in layers[:-1]:
        x = leaky_relu(dense(layer, x, precision))
    return dense(layers[-1], x, precision)


def labels(cfg: MpiNetsConfig) -> np.ndarray:
    """(points, 1) float32 segment labels in cloud order."""
    return np.concatenate([np.full(cfg.robot_points, ROBOT),
                           np.full(cfg.obstacle_points, OBSTACLE),
                           np.full(cfg.target_points, TARGET)]
                          ).astype(np.float32)[:, None]


def target_points(model: MpiNets, q_goal_rad: jax.Array) -> jax.Array:
    """The target: samples on the last link's box at the goal, (B, T, 3)."""
    last = jnp.full(model.target_local.shape[:1], NUM_LINKS - 1, jnp.int32)
    return place(model.target_local, last, q_goal_rad)


def robot_points(model: MpiNets, q: jax.Array) -> jax.Array:
    """The robot's points at normalised joints ``q`` (B, 7): (B, P, 3)."""
    return place(model.robot_local, model.robot_link, unnormalize(q))


def delta(model: MpiNets, obstacles: jax.Array, target: jax.Array,
          q: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One forward pass: (B, 7) joint step at normalised ``q``, and the
    (2,) filled neighbour slots of the two set abstractions."""
    cfg = model.cfg
    precision = PRECISIONS[cfg.precision]
    xyz = jnp.concatenate([robot_points(model, q), obstacles, target], 1)
    feats = jnp.broadcast_to(jnp.asarray(labels(cfg)).T,
                             (xyz.shape[0], 1, xyz.shape[1]))
    enc, fills = encode(cfg.encoder, model.params["pointnet"], xyz, feats,
                        precision)
    with jax.named_scope("policy"):
        joints = _mlp_leaky(model.params["joints"], q, precision)
        d = _mlp_leaky(model.params["decoder"],
                       jnp.concatenate([enc, joints], -1), precision)
    return d, fills


def step(model: MpiNets, obstacles, target, q) -> Tuple[jax.Array,
                                                        jax.Array]:
    """``q_{t+1} = clamp(q_t + delta, -1, 1)``; returns it and the fills."""
    d, fills = delta(model, obstacles, target, q)
    return jnp.clip(q + d, -1.0, 1.0), fills


@jax.jit
def rollout(model: MpiNets, obstacles: jax.Array, q_start: jax.Array,
            q_goal: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Plan B problems: obstacle points (B, obstacle_points, 3), start and
    goal joints in radians (B, 7).  Returns the normalised trajectory
    (B, steps + 1, 7), start included, and the (2,) filled neighbour
    slots of each set abstraction summed over problems and steps."""
    target = target_points(model, q_goal)

    def body(carry, _):
        q, fills = carry
        q, f = step(model, obstacles, target, q)
        return (q, fills + f), q

    q0 = normalize(q_start)
    (_, fills), qs = jax.lax.scan(body, (q0, jnp.zeros((2,), jnp.int32)),
                                  None, length=model.cfg.steps)
    return jnp.concatenate([q0[:, None], jnp.moveaxis(qs, 0, 1)], 1), fills


def bc_loss(model: MpiNets, params, obstacles, target, q, expert
            ) -> jax.Array:
    """Behaviour cloning: mean squared error of the policy's joint step at
    normalised ``q`` against the expert's."""
    d, _ = delta(dataclasses.replace(model, params=params), obstacles,
                 target, q)
    return jnp.mean(jnp.square(d - expert))


__all__ = ["MPINETS", "SMALL", "MpiNets", "MpiNetsConfig", "bc_loss",
           "config_from_dict", "init_mpinets", "normalize", "robot_points",
           "rollout", "step", "target_points", "unnormalize"]
