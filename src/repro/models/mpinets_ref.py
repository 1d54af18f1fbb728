"""Plain reference of the MPiNets forward pass and rollout step.

One problem at a time, in straightforward ``jax.numpy`` float32 with every
matmul at ``highest`` precision, and no Pallas kernel: farthest point
sampling is a loop of argmaxes over every point, ball query a full
distance matrix.  It follows MPiNets' ``model.py`` (Fishman et al., CoRL
2022) and ``pointnet2_ops``' sampling and grouping:

* input: robot, obstacle and target points in that order, each with its
  segment label (0, 1, 2) as the one feature channel;
* SA1: FPS picks 512 centres, the first index 0; ball query r = 0.05 m,
  128 neighbours; shared MLP 4 -> 64 -> 64 -> 64 (bias, ReLU); max;
* SA2: FPS picks 128 of the 512; r = 0.3 m, 128 neighbours; 67 -> 128 ->
  128 -> 256; max;
* GroupAll: every point, xyz not centred, 259 -> 512 -> 512 -> 1024; max;
* head: 1024 -> 4096, GroupNorm(16), LeakyReLU(0.01), 4096 -> 2048,
  GroupNorm(16), LeakyReLU, 2048 -> 2048;
* joints 7 -> 32 -> 64 -> 128 -> 128 -> 64 and decoder [2048 | 64] -> 512
  -> 256 -> 128 -> 7, a LeakyReLU between layers;
* step: ``q_{t+1} = clamp(q_t + delta, -1, 1)`` in normalised joints.

``pointnet2_ops`` details kept: FPS never picks a point whose squared norm
is at most 1e-3 and breaks ties to the lowest index; a neighbour is ``d2 <
r*r``; unfilled slots repeat the first neighbour (0 for an empty ball).

Departures from MPiNets: the robot and target points are samples on the
link boxes of ``repro.core.geometry`` (MPiNets samples the Panda's
meshes), so callers pass the robot points already placed at ``q_t``; the
arm is that module's Panda-like chain with its joint limits; the weights
are seeded random (PyTorch's default init), not trained.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.planner import MpiNetsConfig, labels
from repro.models.pointnet import SetAbstraction

GN_EPS = 1e-5
LEAKY_SLOPE = 0.01
#: pointnet2's FPS skips points with ``x*x + y*y + z*z`` at or below this.
MIN_SQ_NORM = 1e-3


def fps(xyz: jax.Array, m: int) -> jax.Array:
    """(N, 3) -> (m,) int32 farthest point sampling, pointnet2's rules."""
    x, y, z = xyz[:, 0], xyz[:, 1], xyz[:, 2]
    ok = (x * x + y * y) + z * z > MIN_SQ_NORM
    dist = jnp.where(ok, jnp.inf, -1.0).astype(jnp.float32)
    picks = [0]
    for _ in range(m - 1):
        d = xyz - xyz[picks[-1]]
        d2 = (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]) + d[:, 2] * d[:, 2]
        dist = jnp.minimum(d2, dist)
        picks.append(int(jnp.argmax(dist)))
    return jnp.asarray(picks, jnp.int32)


def ball_query(xyz: jax.Array, centres: jax.Array, radius: float, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """(N, 3), (M, 3) -> (idx (M, k) int32, filled (M,) int32)."""
    d = centres[:, None, :] - xyz[None, :, :]
    d2 = (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) + d[..., 2] * d[..., 2]
    r2 = np.float32(radius) * np.float32(radius)
    hit = np.asarray(d2 < r2)
    idx = np.zeros((len(hit), k), np.int32)
    filled = np.zeros(len(hit), np.int32)
    for i, row in enumerate(hit):
        found = np.flatnonzero(row)[:k]
        filled[i] = len(found)
        if len(found):
            idx[i] = found[0]
            idx[i, :len(found)] = found
    return jnp.asarray(idx), jnp.asarray(filled)


def _dense(layer, x):
    return jnp.dot(x, layer["w"]) + layer["b"]


def _leaky(x):
    return jnp.where(x >= 0, x, LEAKY_SLOPE * x)


def set_abstraction(layers, xyz, feats, sa: SetAbstraction):
    """One problem: (N, 3), (N, C) -> (centres (m, 3), (m, C'))."""
    centres = xyz[fps(xyz, sa.centres)]
    idx, _ = ball_query(xyz, centres, sa.radius, sa.neighbours)
    h = jnp.concatenate([xyz[idx] - centres[:, None, :], feats[idx]], -1)
    for layer in layers:
        h = jax.nn.relu(_dense(layer, h))
    return centres, jnp.max(h, axis=1)


def group_norm(norm, x, groups: int):
    """(C,) -> (C,): each channel group by its mean and biased variance."""
    g = x.reshape(groups, -1)
    mean = jnp.mean(g, -1, keepdims=True)
    var = jnp.mean((g - mean) ** 2, -1, keepdims=True)
    return ((g - mean) / jnp.sqrt(var + GN_EPS)).reshape(-1) \
        * norm["scale"] + norm["bias"]


def forward(cfg: MpiNetsConfig, params: Dict, robot: jax.Array,
            obstacles: jax.Array, target: jax.Array, q: jax.Array
            ) -> jax.Array:
    """One problem's joint step ``delta`` (7,) at normalised ``q`` (7,),
    the robot's points already placed at ``q``."""
    with jax.default_matmul_precision("highest"):
        xyz = jnp.concatenate([robot, obstacles, target], 0)
        feats = jnp.asarray(labels(cfg))
        pn = params["pointnet"]
        xyz, feats = set_abstraction(pn["sa1"], xyz, feats, cfg.encoder.sa1)
        xyz, feats = set_abstraction(pn["sa2"], xyz, feats, cfg.encoder.sa2)
        h = jnp.concatenate([xyz, feats], -1)
        for layer in pn["sa_global"]:
            h = jax.nn.relu(_dense(layer, h))
        x = jnp.max(h, axis=0)
        for layer, norm in zip(pn["head"][:-1], pn["norms"]):
            x = _leaky(group_norm(norm, _dense(layer, x), cfg.encoder.groups))
        x = _dense(pn["head"][-1], x)
        j = q
        for layer in params["joints"][:-1]:
            j = _leaky(_dense(layer, j))
        j = _dense(params["joints"][-1], j)
        d = jnp.concatenate([x, j])
        for layer in params["decoder"][:-1]:
            d = _leaky(_dense(layer, d))
        return _dense(params["decoder"][-1], d)


def step(cfg: MpiNetsConfig, params: Dict, robot, obstacles, target, q
         ) -> jax.Array:
    """``clamp(q + delta, -1, 1)`` for one problem."""
    return jnp.clip(q + forward(cfg, params, robot, obstacles, target, q),
                    -1.0, 1.0)
