"""Batched farthest point sampling: every pick of a set abstraction, for a
block of eight problems, in one Pallas call (``fps_sample``).

The block's points stay resident in VMEM as three ``(8, N)`` coordinate
planes, one problem a sublane row, so one pick is one pass over the
block: the distance update ``dist = min(dist, |p - latest|^2)`` and the
argmax are vector work over all eight problems at once, and the loop over
picks runs inside the kernel with no launch between picks.

Semantics are those of ``pointnet2_ops``' ``furthest_point_sampling``: the
first pick is index 0; a point whose squared norm is at most
:data:`MIN_SQ_NORM` is never picked; each later pick is the point farthest
from every pick so far, ties going to the lowest index.  Distances are
float32 and computed as ``(dx*dx + dy*dy) + dz*dz`` with ``d = p - latest``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Problems a block holds: one a sublane row.
BLOCK = 8
LANES = 128
#: pointnet2's FPS skips points with ``x*x + y*y + z*z`` at or below this.
MIN_SQ_NORM = 1e-3


def fps_kernel(xyz_ref, idx_ref, dist_ref, *, n: int, m: int):
    """``xyz_ref`` (3, 8, Np) f32, ``idx_ref`` (8, Mp) int32 out,
    ``dist_ref`` (8, Np) f32 scratch.  Lanes at or past ``n`` are padding."""
    shape = dist_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)
    x, y, z = xyz_ref[0], xyz_ref[1], xyz_ref[2]
    valid = (lane < n) & ((x * x + y * y) + z * z > MIN_SQ_NORM)
    # A point that can never be picked holds -1: below every distance.
    dist_ref[...] = jnp.where(valid, jnp.inf, -1.0).astype(jnp.float32)
    idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)
    big = jnp.int32(shape[1])

    def pick(i, latest):
        lx, ly, lz = latest
        px, py, pz = xyz_ref[0], xyz_ref[1], xyz_ref[2]
        dx, dy, dz = px - lx, py - ly, pz - lz
        dist = jnp.minimum((dx * dx + dy * dy) + dz * dz, dist_ref[...])
        dist_ref[...] = dist
        best = jnp.max(dist, axis=1, keepdims=True)
        besti = jnp.min(jnp.where(dist == best, lane, big), axis=1,
                        keepdims=True)
        idx_ref[...] = jnp.where(col == i, besti, idx_ref[...])
        hit = lane == besti
        return tuple(jnp.sum(jnp.where(hit, p, 0.0), axis=1, keepdims=True)
                     for p in (px, py, pz))

    first = (x[:, 0:1], y[:, 0:1], z[:, 0:1])
    jax.lax.fori_loop(1, m, pick, first)


def make_fps_call(blocks: int, n_pad: int, m_pad: int, n: int, m: int,
                  interpret: bool):
    """``(3, blocks*8, n_pad)`` f32 coordinates -> ``(blocks*8, m_pad)``
    int32 picks (columns past ``m`` are 0)."""
    kernel = functools.partial(fps_kernel, n=n, m=m)
    return pl.pallas_call(
        kernel,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((3, BLOCK, n_pad), lambda b: (0, b, 0))],
        out_specs=pl.BlockSpec((BLOCK, m_pad), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks * BLOCK, m_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((BLOCK, n_pad), jnp.float32)],
        interpret=interpret,
        name="fps_sample",
    )
