"""Fused staged SACT Pallas kernel — the "collision OP unit" on TPU.

RoboGPU §III-C replaces 47 interconnect-hopping TTA+ µops with dedicated
Box-Normal and Edge×Edge OP units so intermediates never leave the unit.
The TPU analogue: one `pallas_call` that keeps an OBB tile and an AABB tile
resident in VMEM and evaluates the *entire* staged test (sphere pre-tests,
6 box-normal axes, 9 edge×edge axes) without materializing any intermediate
in HBM.  Unfused jnp stages move ~424 B/test HBM-side; this kernel moves
~92 B/test (boxes in, verdict out) — see core/counters.py.

Early exit inside the kernel is *predication* (lanes that found a separating
axis stop contributing via masks) plus a *conditional return* at tile
granularity: once every pair in the tile is decided after the box-normal
stage, the edge×edge stage is skipped with `lax.cond` — the per-tile version
of RoboCore's RETURN unit.

Geometry layout: component-unrolled SoA.  3-vectors are awkward on 8×128
vregs, so each component is its own (block,) vector and all 15 axis formulas
are unrolled scalars over the (bm, bn) tile plane — pure VPU code, no MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-6
NUM_AXES = 15


def _load_obb(obb_ref, idx):
    """obb_ref: (bm, 15) packed [center(3) half(3) rot(9 row-major)]."""
    return obb_ref[:, idx]


def sact_tile(t, Rb, A, ahb, ohb, *, use_spheres: bool):
    """Staged SACT over component-unrolled arrays of one common shape.

    Args are the per-pair quantities as plain component lists — ``t``/
    ``ahb``/``ohb`` three arrays each, ``Rb``/``A`` (= |R| + eps) 3x3 nested
    lists — every array sharing one tile shape.  Returns (collide bool,
    exit_code int32) of that shape.  Shape-agnostic so both the dense
    (bm, bn)-plane SACT kernel and the (bn,)-lane fused traversal-step
    kernel share the exact axis formulas (bitwise: same op order).

    Early exit is predication per lane plus a *conditional return* at tile
    granularity: once every pair is decided after the box-normal stage, the
    edge x edge stage is skipped with ``lax.cond`` — the per-tile version
    of RoboCore's RETURN unit.
    """
    shape = t[0].shape
    decided_sep = jnp.zeros(shape, jnp.bool_)
    exit_code = jnp.full(shape, 17, jnp.int32)

    def note_sep(decided, code, sep_now, code_val):
        newly = sep_now & ~decided
        return decided | sep_now, jnp.where(newly, code_val, code)

    # --- stage 0/1: sphere pre-tests (optional) ------------------------
    confirmed_hit = jnp.zeros(shape, jnp.bool_)
    if use_spheres:
        d2 = jnp.zeros(shape, jnp.float32)
        for i in range(3):
            d = jnp.maximum(jnp.abs(t[i]) - ahb[i], 0.0)
            d2 = d2 + d * d
        r_out2 = ohb[0] * ohb[0] + ohb[1] * ohb[1] + ohb[2] * ohb[2]
        r_in = jnp.minimum(jnp.minimum(ohb[0], ohb[1]), ohb[2])
        decided_sep, exit_code = note_sep(decided_sep, exit_code,
                                          d2 > r_out2, 0)
        newly_hit = (d2 < r_in * r_in) & ~decided_sep
        confirmed_hit = confirmed_hit | newly_hit
        exit_code = jnp.where(newly_hit, 1, exit_code)

    live0 = ~(decided_sep | confirmed_hit)

    # --- stage A: 6 box-normal axes ------------------------------------
    for i in range(3):   # L = A_i
        rb = ohb[0] * A[i][0] + ohb[1] * A[i][1] + ohb[2] * A[i][2]
        sep = (jnp.abs(t[i]) > ahb[i] + rb) & live0
        decided_sep, exit_code = note_sep(decided_sep, exit_code, sep, 2 + i)
    for j in range(3):   # L = B_j
        lhs = jnp.abs(t[0] * Rb[0][j] + t[1] * Rb[1][j] + t[2] * Rb[2][j])
        ra = ahb[0] * A[0][j] + ahb[1] * A[1][j] + ahb[2] * A[2][j]
        sep = (lhs > ra + ohb[j]) & live0
        decided_sep, exit_code = note_sep(decided_sep, exit_code, sep, 5 + j)

    # --- stage B: 9 edge x edge axes, tile-level conditional return ----
    def edge_stage(decided_sep, exit_code):
        live = live0 & ~decided_sep
        for i in range(3):
            i1, i2 = (i + 1) % 3, (i + 2) % 3
            for j in range(3):
                j1, j2 = (j + 1) % 3, (j + 2) % 3
                ra = ahb[i1] * A[i2][j] + ahb[i2] * A[i1][j]
                rb = ohb[j1] * A[i][j2] + ohb[j2] * A[i][j1]
                lhs = jnp.abs(t[i2] * Rb[i1][j] - t[i1] * Rb[i2][j])
                sep = (lhs > ra + rb) & live
                decided_sep, exit_code = note_sep(decided_sep, exit_code,
                                                  sep, 8 + 3 * i + j)
        return decided_sep, exit_code

    # The cond carries the decided mask as int32: Mosaic cannot return
    # boolean vectors from a branch.
    def edge_stage_i32(decided, exit_code):
        d, e = edge_stage(decided != 0, exit_code)
        return d.astype(jnp.int32), e

    all_decided = jnp.sum(jnp.where(decided_sep | confirmed_hit, 0, 1)) == 0
    decided_i32, exit_code = jax.lax.cond(
        all_decided, lambda d, e: (d, e), edge_stage_i32,
        decided_sep.astype(jnp.int32), exit_code)
    decided_sep = decided_i32 != 0

    collide = (~decided_sep) | confirmed_hit
    return collide, exit_code


def sact_kernel(obb_ref, aabb_ref, collide_ref, exit_ref, *,
                use_spheres: bool):
    bm = obb_ref.shape[0]
    bn = aabb_ref.shape[0]

    # --- unpack (component-unrolled) -----------------------------------
    oc = [obb_ref[:, i] for i in range(3)]            # obb centre
    oh = [obb_ref[:, 3 + i] for i in range(3)]        # obb half extents
    # rot row-major: R[i][j] = obb_ref[:, 6 + 3*i + j]
    R = [[obb_ref[:, 6 + 3 * i + j] for j in range(3)] for i in range(3)]
    ac = [aabb_ref[:, i] for i in range(3)]
    ah = [aabb_ref[:, 3 + i] for i in range(3)]

    def bc_m(x):  # (bm,) -> (bm, bn)
        return jnp.broadcast_to(x[:, None], (bm, bn))

    def bc_n(x):  # (bn,) -> (bm, bn)
        return jnp.broadcast_to(x[None, :], (bm, bn))

    t = [bc_m(oc[i]) - bc_n(ac[i]) for i in range(3)]
    Rb = [[bc_m(R[i][j]) for j in range(3)] for i in range(3)]
    A = [[jnp.abs(Rb[i][j]) + _EPS for j in range(3)] for i in range(3)]
    ahb = [bc_n(ah[i]) for i in range(3)]
    ohb = [bc_m(oh[i]) for i in range(3)]

    collide, exit_code = sact_tile(t, Rb, A, ahb, ohb,
                                   use_spheres=use_spheres)
    collide_ref[...] = collide
    exit_ref[...] = exit_code


def make_sact_call(m_pad: int, n_pad: int, bm: int, bn: int,
                   use_spheres: bool, interpret: bool):
    """Build the pallas_call for padded sizes (m_pad, n_pad)."""
    kernel = functools.partial(sact_kernel, use_spheres=use_spheres)
    return pl.pallas_call(
        kernel,
        grid=(m_pad // bm, n_pad // bn),
        in_specs=[
            pl.BlockSpec((bm, 15), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 6), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, n_pad), jnp.bool_),
            jax.ShapeDtypeStruct((m_pad, n_pad), jnp.int32),
        ],
        interpret=interpret,
    )
