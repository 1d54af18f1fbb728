"""Fused traversal-step Pallas kernel — one wavefront level, boxes in,
verdict words out.

RoboGPU's RoboCore (§III-C) fuses the staged collision test with the
traversal control flow so intermediates never leave the unit.  The TPU
analogue for the wavefront engine: one `pallas_call` per octree level whose
grid walks the fixed-capacity frontier in blocks of ``bn`` pairs.  Every
per-pair quantity is laid out lane-dense, ``(bn // 128, 128)`` per field,
so each block is whole (8, 128) vreg tiles.  Each block

  1. reads its pairs' query OBB fields — gathered by ``q_idx`` in the
     glue (:mod:`repro.kernels.traverse.ops`) into a ``(15, cap/128, 128)``
     field-major slab, so no query table is resident in VMEM;
  2. reconstructs the frontier nodes' AABBs from their Morton codes
     in-register (bit twiddling, no HBM lookup);
  3. runs the staged SACT via :func:`repro.kernels.sact.kernel.sact_tile` —
     the exact axis formulas of the dense SACT kernel, including the
     tile-level conditional return that skips the 9 edge x edge axes once
     every lane in the block is decided (phase 2 of the two-phase frontier
     cull; phase 1 is the sphere + box-normal stage);
  4. probes terminality from the gathered ``full`` flag / leaf-level scalar;
  5. emits ONE packed int32 word per pair (collide | is_term<<1 | exit<<2).

Blocks that lie entirely at or past ``n_live`` write zeros without reading
their inputs — the whole-tile analogue of frontier retirement, which is
what stream compaction between levels buys: decided pairs do not just mask
off, their tiles are never scheduled.  The expansion mask and CSR child
codes are pure bit arithmetic on this word plus the frontier's CSR columns,
feeding the stream compaction of :mod:`repro.kernels.compact` — the
searchsorted occupancy probe of the unfused path never runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.octree import _jnp_compact1by2
# _EPS is shared with the dense SACT kernel and core/sact.py: the bitwise
# fused-vs-unfused identity depends on all arms using the same epsilon.
from repro.kernels.sact.kernel import _EPS, sact_tile

#: Lane width of the frontier layout: pair ``p`` sits at (p // 128, p % 128).
LANES = 128


def traverse_kernel(scal_i_ref, scal_f_ref, obb_ref, lane_ref, packed_ref,
                    *, bn: int, use_spheres: bool):
    j = pl.program_id(0)
    n_live = scal_i_ref[0]
    is_leaf = scal_i_ref[1]
    cell = scal_f_ref[0]
    rows = bn // LANES

    @pl.when(j * bn >= n_live)
    def _retired_tile():
        packed_ref[...] = jnp.zeros((rows, LANES), jnp.int32)

    @pl.when(j * bn < n_live)
    def _live_tile():
        oc = [obb_ref[i] for i in range(3)]
        oh = [obb_ref[3 + i] for i in range(3)]
        R = [[obb_ref[6 + 3 * i + k] for k in range(3)] for i in range(3)]

        # -- node AABB from Morton code (in-register) -------------------
        code = lane_ref[0]
        node_c = [scal_f_ref[1 + i]
                  + (_jnp_compact1by2(code >> i).astype(jnp.int32)
                     .astype(jnp.float32) + 0.5) * cell
                  for i in range(3)]
        node_h = cell * 0.5

        # -- staged SACT, shared tile formulas + conditional return -----
        t = [oc[i] - node_c[i] for i in range(3)]
        A = [[jnp.abs(R[i][k]) + _EPS for k in range(3)] for i in range(3)]
        collide, exit_code = sact_tile(t, R, A, [node_h] * 3, oh,
                                       use_spheres=use_spheres)

        # -- terminality + packed verdict word --------------------------
        is_term = (lane_ref[1] != 0) | (is_leaf != 0)
        pair = (j * bn
                + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
                * LANES
                + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
        packed = (collide.astype(jnp.int32)
                  | (is_term.astype(jnp.int32) << 1)
                  | (exit_code << 2))
        packed_ref[...] = jnp.where(pair < n_live, packed, 0)


def make_traverse_call(capacity: int, bn: int, use_spheres: bool,
                       interpret: bool):
    """Build the pallas_call for one traversal step at a given capacity.

    Inputs: scal_i (2,) int32 [n_live, is_leaf] and scal_f (4,) f32
    [cell, scene_lo xyz] in SMEM; the gathered OBB fields
    (15, capacity/128, 128) f32; the frontier's (code, full) lanes
    (2, capacity/128, 128) int32.  Output: packed (capacity/128, 128)
    int32 words.  ``capacity`` and ``bn`` are multiples of 1024, so every
    block is whole (8, 128) tiles.
    """
    assert bn % (8 * LANES) == 0 and capacity % bn == 0, (capacity, bn)
    rows = bn // LANES
    kernel = functools.partial(traverse_kernel, bn=bn,
                               use_spheres=use_spheres)
    return pl.pallas_call(
        kernel,
        grid=(capacity // bn,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scal_i
            pl.BlockSpec(memory_space=pltpu.SMEM),            # scal_f
            pl.BlockSpec((15, rows, LANES), lambda j: (0, j, 0)),
            pl.BlockSpec((2, rows, LANES), lambda j: (0, j, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANES), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((capacity // LANES, LANES),
                                       jnp.int32),
        interpret=interpret,
        name="traverse_step",
    )
