"""PointNet++ encoder of MPiNets (Fishman et al., CoRL 2022, ``model.py``):
two set abstractions, a GroupAll set abstraction and a GroupNorm head.

A set abstraction samples ``centres`` points by farthest point sampling,
groups the first ``neighbours`` points (by index) within ``radius`` of
each centre as ``[xyz - centre, features]``, runs a shared MLP (a bias and
a ReLU after every layer) over every neighbour and max-pools over the
neighbours.  Sampling and the ball query are the Pallas kernels of
:mod:`repro.kernels.fps` and :mod:`repro.kernels.ballquery`, with
``pointnet2_ops`` semantics; the gather of the groups and the MLPs are
XLA.  GroupAll takes every point, its xyz not centred.  The head is
``Linear, GroupNorm, LeakyReLU, Linear, GroupNorm, LeakyReLU, Linear``.

Weights are float32 dicts ``{"w": (fan_in, fan_out), "b": (fan_out,)}``;
every matmul takes the ``precision`` the configuration states.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ballquery import ball_query
from repro.kernels.fps import fps_sample

#: LeakyReLU's slope below zero (PyTorch's default).
LEAKY_SLOPE = 0.01
#: GroupNorm's epsilon (PyTorch's default).
GN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class SetAbstraction:
    centres: int
    radius: float
    neighbours: int
    widths: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class PointNetConfig:
    sa1: SetAbstraction
    sa2: SetAbstraction
    global_widths: Tuple[int, ...]
    head_widths: Tuple[int, ...]
    groups: int = 16
    in_features: int = 1          # the segment label


def torch_linear(rng: np.random.Generator, fan_in: int, fan_out: int
                 ) -> Dict[str, jax.Array]:
    """PyTorch's default ``Linear`` / 1x1 ``Conv2d`` init: weight and bias
    uniform in +-1/sqrt(fan_in)."""
    bound = 1.0 / np.sqrt(fan_in)
    w = rng.uniform(-bound, bound, (fan_in, fan_out)).astype(np.float32)
    b = rng.uniform(-bound, bound, fan_out).astype(np.float32)
    return {"w": jnp.asarray(w), "b": jnp.asarray(b)}


def mlp_init(rng, fan_in: int, widths) -> List[Dict[str, jax.Array]]:
    """One :func:`torch_linear` layer for each width, in order."""
    layers = []
    for w in widths:
        layers.append(torch_linear(rng, fan_in, w))
        fan_in = w
    return layers


def init_pointnet(cfg: PointNetConfig, rng: np.random.Generator) -> Dict:
    c1 = cfg.sa1.widths[-1]
    c2 = cfg.sa2.widths[-1]
    head = mlp_init(rng, cfg.global_widths[-1], cfg.head_widths)
    norms = [{"scale": jnp.ones((w,), jnp.float32),
              "bias": jnp.zeros((w,), jnp.float32)}
             for w in cfg.head_widths[:-1]]
    return {"sa1": mlp_init(rng, 3 + cfg.in_features, cfg.sa1.widths),
            "sa2": mlp_init(rng, 3 + c1, cfg.sa2.widths),
            "sa_global": mlp_init(rng, 3 + c2, cfg.global_widths),
            "head": head, "norms": norms}


def dense(layer, x, precision) -> jax.Array:
    return jnp.dot(x, layer["w"], precision=precision) + layer["b"]


def shared_mlp(layers, x, precision) -> jax.Array:
    """The same MLP on every point of channels-first ``x`` (B, C, ...),
    a bias and a ReLU after each layer."""
    for layer in layers:
        x = jnp.einsum("bc...,cd->bd...", x, layer["w"], precision=precision)
        x = jax.nn.relu(x + layer["b"].reshape((-1,) + (1,) * (x.ndim - 2)))
    return x


def leaky_relu(x: jax.Array) -> jax.Array:
    return jnp.where(x >= 0, x, LEAKY_SLOPE * x)


def group_norm(norm, x: jax.Array, groups: int) -> jax.Array:
    """GroupNorm of (B, C) features: each of ``groups`` channel groups
    normalised by its own mean and biased variance, then scaled."""
    B, C = x.shape
    g = x.reshape(B, groups, C // groups)
    mean = jnp.mean(g, -1, keepdims=True)
    var = jnp.mean(jnp.square(g - mean), -1, keepdims=True)
    g = (g - mean) / jnp.sqrt(var + GN_EPS)
    return g.reshape(B, C) * norm["scale"] + norm["bias"]


def set_abstraction(layers, xyz: jax.Array, feats: jax.Array,
                    sa: SetAbstraction, precision
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B, N, 3) points, (B, C, N) features -> (centres (B, m, 3),
    features (B, C', m), filled neighbour slots (B, m) int32).

    Features stay channels-first, so that a group's gather and every
    MLP layer lay the ``m * k`` neighbours along the minor axis."""
    B = xyz.shape[0]
    idx = fps_sample(xyz, sa.centres)                          # (B, m)
    centres = jnp.take_along_axis(xyz, idx[..., None], axis=1)
    nbr, filled = ball_query(xyz, centres, sa.radius, sa.neighbours)
    table = jnp.concatenate([jnp.swapaxes(xyz, 1, 2), feats], 1)
    g = jax.vmap(lambda t, i: t[:, i])(table, nbr.reshape(B, -1))
    g = g.reshape(B, -1, sa.centres, sa.neighbours)           # (B, 3+C, m, k)
    rel = g[:, :3] - jnp.swapaxes(centres, 1, 2)[..., None]
    h = shared_mlp(layers, jnp.concatenate([rel, g[:, 3:]], 1), precision)
    return centres, jnp.max(h, axis=3), filled


def group_all(layers, xyz: jax.Array, feats: jax.Array, precision
              ) -> jax.Array:
    """GroupAll set abstraction: (B, N, 3), (B, C, N) -> (B, C')."""
    h = jnp.concatenate([jnp.swapaxes(xyz, 1, 2), feats], 1)
    return jnp.max(shared_mlp(layers, h, precision), axis=2)


def encode(cfg: PointNetConfig, params, xyz: jax.Array, feats: jax.Array,
           precision) -> Tuple[jax.Array, jax.Array]:
    """(B, N, 3) points with (B, C, N) features -> ((B, head_widths[-1])
    encoding, (2,) int32 filled neighbour slots summed per set
    abstraction)."""
    with jax.named_scope("sa1"):
        xyz, feats, fill1 = set_abstraction(params["sa1"], xyz, feats,
                                            cfg.sa1, precision)
    with jax.named_scope("sa2"):
        xyz, feats, fill2 = set_abstraction(params["sa2"], xyz, feats,
                                            cfg.sa2, precision)
    with jax.named_scope("sa_global"):
        x = group_all(params["sa_global"], xyz, feats, precision)
    with jax.named_scope("fc"):
        head = params["head"]
        for layer, norm in zip(head[:-1], params["norms"]):
            x = leaky_relu(group_norm(norm, dense(layer, x, precision),
                                      cfg.groups))
        x = dense(head[-1], x, precision)
    return x, jnp.stack([jnp.sum(fill1), jnp.sum(fill2)])
