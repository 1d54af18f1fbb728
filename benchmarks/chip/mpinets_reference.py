"""Plain reference of one MPiNets step, independent of the program.

The published network (Fishman et al., CoRL 2022, ``model.py`` over
``pointnet2_ops``) written out in numpy for one problem at a time, and the
benchmark's judge of the policy (``loops/plan.py``).  It imports nothing of
the program.  What it is given:

* ``model``: the configuration's ``"model"`` object (widths, radii,
  neighbours, centres);
* ``weights``: the program's weights as numpy arrays, in the program's
  nesting (``pointnet`` ``sa1``/``sa2``/``sa_global``/``head`` lists of
  ``{"w", "b"}``, ``norms`` of ``{"scale", "bias"}``; ``joints``;
  ``decoder``), the checkpoint under test;
* the step's input: robot points (placed at ``q_t``), obstacle and target
  points, float32, and the normalised joints ``q_t``.

Sampling and grouping run in float32 in the program's order of operations,
so that they pick the same points from the same cloud: farthest point
sampling from index 0, ``d2 = (dx*dx + dy*dy) + dz*dz``, a point whose
squared norm is at most 1e-3 never picked, ties to the lowest index; ball
query's neighbours are ``d2 < r*r`` (``d = centre - point``), the first
``k`` by index, unfilled slots repeating the first.  Every dense layer,
GroupNorm and the clamp run in float64.  Segment labels: robot 0,
obstacle 1, target 2.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

MIN_SQ_NORM = np.float32(1e-3)
GN_EPS = 1e-5
LEAKY_SLOPE = 0.01


def _sq(d: np.ndarray) -> np.ndarray:
    return (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) \
        + d[..., 2] * d[..., 2]


def fps(xyz: np.ndarray, m: int) -> np.ndarray:
    """(N, 3) float32 -> (m,) indices."""
    xyz = np.asarray(xyz, np.float32)
    dist = np.where(_sq(xyz) > MIN_SQ_NORM, np.float32(np.inf),
                    np.float32(-1.0))
    picks = [0]
    for _ in range(m - 1):
        dist = np.minimum(_sq(xyz - xyz[picks[-1]]), dist)
        picks.append(int(np.argmax(dist)))
    return np.asarray(picks)


def ball_query(xyz: np.ndarray, centres: np.ndarray, radius: float, k: int
               ) -> np.ndarray:
    """(N, 3), (M, 3) float32 -> (M, k) indices."""
    r2 = np.float32(radius) * np.float32(radius)
    hit = _sq(np.asarray(centres, np.float32)[:, None, :]
              - np.asarray(xyz, np.float32)[None, :, :]) < r2
    idx = np.zeros((len(hit), k), np.int64)
    for i, row in enumerate(hit):
        found = np.flatnonzero(row)[:k]
        if len(found):
            idx[i] = found[0]
            idx[i, :len(found)] = found
    return idx


def _dense(layer, x):
    return x @ np.asarray(layer["w"], np.float64) \
        + np.asarray(layer["b"], np.float64)


def _relu_mlp(layers, x):
    for layer in layers:
        x = np.maximum(_dense(layer, x), 0.0)
    return x


def _leaky_mlp(layers, x):
    for layer in layers[:-1]:
        x = _dense(layer, x)
        x = np.where(x >= 0, x, LEAKY_SLOPE * x)
    return _dense(layers[-1], x)


def _group_norm(norm, x, groups: int):
    g = x.reshape(groups, -1)
    g = (g - g.mean(-1, keepdims=True)) / np.sqrt(g.var(-1, keepdims=True)
                                                  + GN_EPS)
    return g.reshape(-1) * np.asarray(norm["scale"], np.float64) \
        + np.asarray(norm["bias"], np.float64)


def _set_abstraction(layers, xyz, feats, sa: Dict):
    """(N, 3) float32, (N, C) float64 -> ((m, 3) float32, (m, C'))."""
    centres = xyz[fps(xyz, sa["centres"])]
    idx = ball_query(xyz, centres, sa["radius"], sa["neighbours"])
    rel = xyz[idx].astype(np.float64) - centres[:, None, :]
    h = _relu_mlp(layers, np.concatenate([rel, feats[idx]], -1))
    return centres, h.max(axis=1)


def step(model: Dict, weights: Dict, robot, obstacles, target, q
         ) -> np.ndarray:
    """``clamp(q + delta, -1, 1)`` (7,) float64 for one problem."""
    enc = model["encoder"]
    xyz = np.concatenate([robot, obstacles, target]).astype(np.float32)
    feats = np.concatenate([np.zeros(len(robot)), np.ones(len(obstacles)),
                            np.full(len(target), 2.0)])[:, None]
    pn = weights["pointnet"]
    xyz, feats = _set_abstraction(pn["sa1"], xyz, feats, enc["sa1"])
    xyz, feats = _set_abstraction(pn["sa2"], xyz, feats, enc["sa2"])
    x = _relu_mlp(pn["sa_global"],
                  np.concatenate([xyz.astype(np.float64), feats], -1)
                  ).max(axis=0)
    for layer, norm in zip(pn["head"][:-1], pn["norms"]):
        x = _group_norm(norm, _dense(layer, x), enc["groups"])
        x = np.where(x >= 0, x, LEAKY_SLOPE * x)
    x = _dense(pn["head"][-1], x)
    q = np.asarray(q, np.float64)
    joints = _leaky_mlp(weights["joints"], q)
    delta = _leaky_mlp(weights["decoder"], np.concatenate([x, joints]))
    return np.clip(q + delta, -1.0, 1.0)
