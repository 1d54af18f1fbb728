"""Fused wavefront traversal step: one level, frontier in / frontier out.

``traverse_step`` is the loop body of the ``wavefront_fused`` engine: it
takes the live (query, CSR node index) frontier pairs and returns the next
level's compacted pairs plus the updated verdicts — the only per-level
HBM-resident intermediates of the fused path.  Compare the
unfused device arm, which materializes ~5 capacity-sized arrays per level
(the 4-field SactResult, two searchsorted probe vectors, the 8x-expanded
candidate codes, and the compaction scratch).

The staged test dispatches on the backend: the Pallas traversal-step
kernel on TPU (or ``interpret=True`` for CPU validation — untenable inside
real traversals because interpret mode unrolls one program per grid step
at trace time), and the jnp two-phase reference elsewhere.
Both arms share this glue, so verdicts, exit codes, and the CSR expansion
are backend-independent; and both cull in two phases — spheres + box-normal
axes decide most pairs, the edge axes run only when survivors remain
(``lax.cond`` batch-wide in jnp, per-tile in the kernel).

Child expansion is O(1) per candidate: occupancy is bit ``j`` of the node's
8-bit CSR child mask, the child's code is ``(code << 3) | j``, and its node
index is ``child_start + popcount(mask & ((1 << j) - 1))`` — no
searchsorted over the level's code array anywhere in the loop body.  The
node index also makes the Morton code *redundant in the frontier*: codes
are re-gathered from the level's code row on entry, so the compaction
moves (query, node index) pairs — no wider than the unfused arm's
(query, code) pairs despite the extra CSR capability.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.octree import DeviceOctree, node_centers_from_codes
from repro.core.quantize import BF16_START_BITS, U8_START_BITS
from repro.core.sact import (SactResult, axis_tests_from_exit,
                             mask_frontier_result, payload_min_update,
                             sact_frontier_staged)
from repro.kernels.compact.ops import compact_pairs
from repro.kernels.persist.ref import csr_child_slots
from repro.kernels.sact.ops import pack_obbs
from repro.kernels.traverse.kernel import LANES, make_traverse_call
from repro.kernels.traverse.ref import unpack_verdicts


def _use_pallas_default() -> bool:
    return jax.default_backend() == "tpu"


def _test_pallas(obb_c, obb_h, obb_r, q_idx, codes, full_l, cell, scene_lo,
                 is_leaf, n_live, use_spheres: bool, bn: int,
                 interpret: bool):
    """Pallas arm: packed verdict words for the whole frontier.

    The query OBBs are gathered here, by XLA, into the kernel's
    field-major lane-dense slab: a VMEM-resident OBB table with an
    in-kernel one-hot gather does not scale to paper-size batches.
    """
    capacity = q_idx.shape[0]
    pad = (-capacity) % bn
    rows = (capacity + pad) // LANES
    obb = pack_obbs(obb_c, obb_h, obb_r)[
        jnp.clip(q_idx, 0, obb_c.shape[0] - 1)]
    obb = jnp.pad(obb, ((0, pad), (0, 0))).T.reshape(15, rows, LANES)
    lanes = jnp.stack([
        jnp.pad(jax.lax.bitcast_convert_type(codes, jnp.int32), (0, pad)),
        jnp.pad(full_l.astype(jnp.int32), (0, pad))]).reshape(2, rows, LANES)
    scal_i = jnp.stack([jnp.asarray(n_live, jnp.int32),
                        jnp.asarray(is_leaf, jnp.int32)])
    scal_f = jnp.concatenate([jnp.asarray(cell, jnp.float32).reshape(1),
                              jnp.asarray(scene_lo, jnp.float32)])
    call = make_traverse_call(capacity + pad, bn, use_spheres, interpret)
    packed = call(scal_i, scal_f, obb, lanes)
    return packed.reshape(-1)[:capacity]


def traverse_step(obb_c, obb_h, obb_r, dev: DeviceOctree, level, n_live,
                  q_idx, node_idx, verdict, *, use_spheres: bool,
                  use_pallas: Optional[bool] = None,
                  interpret: Optional[bool] = None, bn: int = 1024,
                  owner=None, payload=None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                             dict]:
    """One fused wavefront level for a single scene / query set.

    Pure function of device arrays (level / n_live may be traced); composes
    under jit, vmap, and ``lax.while_loop``.  Returns
    ``(n_next, q_next, idx_next, verdict, info)`` where ``info`` carries the
    per-pair quantities the work model accounts (valid / is_term /
    SactResult / codes / n_new).

    ``verdict`` is the (M,) bool collide array, or — when the plan carries
    owner / payload lanes (:mod:`repro.engine.plan`) — the (G,) int32
    per-group ``best`` array: a terminal hit folds the pair's payload in
    with a min, and a pair expands only while its payload could still beat
    its group's best, which compacts first-hit-decided groups out of the
    frontier exactly like decided waypoint lanes.  The Pallas verdict
    kernel is unchanged either way: it emits per-pair packed words, and the
    payload fold happens in this glue.
    """
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    capacity = q_idx.shape[0]
    lane = jnp.arange(capacity, dtype=jnp.int32)
    valid = lane < n_live
    depth = dev.depth

    def level_row(arr):
        return jax.lax.dynamic_index_in_dim(arr, level, keepdims=False)

    cell = level_row(dev.cell_sizes)
    n_max = dev.codes.shape[-1]
    idx_c = jnp.clip(node_idx, 0, n_max - 1)
    # One (cap, words) gather for all per-node metadata.  Compressed
    # formats (repro.core.quantize) pack topology into word 0; geometry
    # comes from the retained per-level code plane, which the fused arm
    # keeps resident anyway (the Pallas verdict kernel takes codes as an
    # input), so the decode adds no gathers.
    fmt = getattr(dev, "meta_format", "fp32")
    meta = level_row(dev.node_meta)[idx_c]
    if fmt == "fp32":
        codes = jax.lax.bitcast_convert_type(meta[:, 0], jnp.uint32)
        full_l = meta[:, 1] != 0
        child_start = meta[:, 2]
        child_mask = meta[:, 3]
    else:
        w0 = meta[:, 0]
        full_l = w0 < 0
        child_mask = w0 & 0xFF
        start_bits = BF16_START_BITS if fmt == "bf16" else U8_START_BITS
        child_start = (w0 >> 8) & ((1 << start_bits) - 1)
        codes = level_row(dev.codes)[idx_c]
    is_leaf = level == depth

    if use_pallas:
        packed = _test_pallas(obb_c, obb_h, obb_r, q_idx, codes, full_l,
                              cell, dev.scene_lo, is_leaf, n_live,
                              use_spheres, bn, interpret)
        collide_raw, is_term, exit_code = unpack_verdicts(packed)
        n_sphere = jnp.full((capacity,), 2 if use_spheres else 0, jnp.int32)
        res = mask_frontier_result(
            SactResult(collide=collide_raw, exit_code=exit_code,
                       axis_tests=axis_tests_from_exit(exit_code),
                       sphere_tests=n_sphere), valid)
        is_term = is_term | is_leaf
    else:
        node_c, node_h = node_centers_from_codes(codes, dev.scene_lo, cell)
        res = sact_frontier_staged(obb_c[q_idx], obb_h[q_idx], obb_r[q_idx],
                                   node_c, node_h, valid,
                                   use_spheres=use_spheres)
        is_term = jnp.where(is_leaf, True, full_l)

    overlap = res.collide & valid
    term_hit = overlap & is_term
    if owner is not None or payload is not None:
        pay = (jnp.zeros(q_idx.shape, jnp.int32) if payload is None
               else payload[q_idx])
        own = q_idx if owner is None else owner[q_idx]
        verdict = payload_min_update(verdict, own, pay, term_hit)
        undecided = pay < verdict[own]
    else:
        verdict = verdict.at[q_idx].max(term_hit)
        undecided = ~verdict[q_idx]

    # ---- O(1) CSR expansion + on-device stream compaction -------------
    occupied, offs = csr_child_slots(child_mask)                   # (cap, 8)
    cand_idx = child_start[:, None] + offs
    # Early exit: decided queries/groups retire their whole wavefront share.
    expand = overlap & ~is_term & undecided
    child_live = (expand[:, None] & occupied).reshape(-1)          # (cap*8,)
    n_new = jnp.sum(child_live.astype(jnp.int32))
    cnt, q_next, idx_next = compact_pairs(
        child_live, jnp.repeat(q_idx, 8),
        cand_idx.reshape(-1).astype(jnp.uint32), capacity)
    idx_next = idx_next.astype(jnp.int32)
    info = dict(valid=valid, is_term=is_term, res=res, codes=codes,
                n_new=n_new)
    return cnt, q_next, idx_next, verdict, info
