"""jnp oracle for the persistent whole-traversal megakernel.

Contract (shared with kernel.py): run the ENTIRE multi-level wavefront
traversal in one compiled call — level loop inside, frontier never
re-entering the caller between levels — and return exactly the
``(collide, stats)`` pair of the per-level fused arm
(:func:`repro.core.wavefront._traverse_fused`), bitwise, including every
work counter.

Two structural ideas carry the wall-clock win of the persistent mode and
both mirror the kernel:

1. **Live-prefix scheduling.**  The kernel never schedules frontier tiles
   at or past ``n_live``; the jnp analogue processes each level at the
   smallest power-of-two width >= ``n_live`` (a ``lax.switch`` over
   pre-compiled widths) instead of always paying the full static
   ``capacity``.  Lanes in ``[n_live, w)`` are masked exactly as the fused
   arm masks ``[n_live, capacity)``, so verdicts and counters cannot
   change — only dead-lane work disappears.  On typical scenes the live
   frontier is ~5-20x smaller than the escalation bucket.

2. **In-register CSR expansion/compaction.**  Instead of materializing the
   8x-expanded candidate list and stream-compacting ``8 * capacity`` lanes
   (cumsum + 2-channel scatter), survivors' children are placed directly:
   per-parent child counts (popcount of the CSR occupancy mask) are
   exclusive-scanned over ``w`` parents, and child ``j`` of parent ``i``
   lands at ``base[i] + popcount(mask[i] & ((1 << j) - 1))`` — the same
   ascending (parent-major, octant-minor) order the stream compactor
   produces, at 1/8th the scan length.  Children past ``capacity`` drop
   (highest positions first) and are counted in ``overflow``, identical to
   the fused arm's clamp.

The same function serves the ragged multi-scene frontier: with
``scene_of_query`` given, pairs are (scene, query, CSR node) triples over a
:class:`repro.core.octree.MultiSceneOctree` flat table — per-pair cell size
and scene origin are gathers by scene id, and scene ``s``'s root is flat
node ``s`` of the level-0 row.  One compiled call and one compaction pool
serve arbitrarily mixed scene sizes with no per-scene padding.

**Streamed-layout window model.**  Under the kernel's streamed metadata
layout (DESIGN.md §3) each query tile iterates a level through fixed-size
sub-level windows of ``stream_wsub`` rows over its OWN scene's sub-extent
of the (possibly concatenated multi-scene) level row, DMAing only the
row-exact occupied span of each window it actually touches.  With
``stream_bq`` / ``stream_wsub`` / ``scene_off`` / ``scene_counts`` /
``scene_of_tile`` given, the ref accumulates the *identical* schedule into
the ``meta_rows`` stat: lane query ids stay sorted through the
in-register compaction (children inherit their parent's query,
parent-major), so a kernel tile touches window ``w`` at level ``l``
exactly when some valid lane has ``q // bq == t`` and ``(node - off) //
wsub == w`` on the global pool — bitwise on every clean run, like the
other counters.  The fetched span of a touched window is its occupied
extent clipped to the window and rounded OUT to whole
:data:`repro.core.octree.META_ROW_ALIGN`-row DMA chunks (``floor128(lo) ..
ceil128(hi)``), the kernel's exact descriptor arithmetic.

**Gather model.**  With ``tile_bq`` given, the ``meta_gathers`` stat
counts the kernel's metadata gather products: one per (tile, chunk,
window) that holds a valid lane at a level, where a lane's chunk is its
position in its tile's frontier over :data:`CHUNK` and its window that
of the layout (:data:`RESIDENT_WINDOW` rows resident, ``stream_wsub``
rows from the tile scene's sub-extent streamed).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import sact as sact_mod
from repro.core.counters import NUM_EXIT_CODES
from repro.core.octree import (MAX_DEPTH, META_ROW_ALIGN, _jnp_compact1by2,
                               node_centers_from_xyz)
from repro.core.quantize import (BF16_START_BITS, GRID_BITS, META_FORMATS,
                                 U8_START_BITS)
from repro.core.sact import NUM_AXES, PAYLOAD_INF, payload_min_update

#: The megakernel's schedule, shared with kernel.py so the models here
#: cannot drift from it: frontier lanes per pass and rows per gather window
#: of the resident layout (8 sheets of 128 rows).
CHUNK = 256
RESIDENT_WINDOW = 8 * META_ROW_ALIGN


def frontier_widths(capacity: int, w_min: int = 128) -> Tuple[int, ...]:
    """Power-of-two processing widths from ``w_min`` up to ``capacity``."""
    widths = []
    w = min(w_min, capacity)
    while w < capacity:
        widths.append(w)
        w *= 2
    widths.append(capacity)
    return tuple(widths)


def csr_child_slots(child_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """CSR occupancy mask (K,) int32 -> (occupied (K, 8) bool, offs (K, 8)).

    ``offs[i, j] = popcount(mask[i] & ((1 << j) - 1))`` is both the child's
    rank among its parent's occupied octants and its offset from the
    parent's ``child_start`` — shared by the fused step, the persistent
    ref, and the megakernel.
    """
    eight = jnp.arange(8, dtype=jnp.int32)
    occupied = ((child_mask[:, None] >> eight[None, :]) & 1) != 0
    below = (jnp.int32(1) << eight) - 1
    offs = jax.lax.population_count(child_mask[:, None] & below[None, :])
    return occupied, offs


def decode_meta_words(words, meta_format: str, level, pcode=None):
    """In-register dequantize of gathered packed metadata words.

    Shared by the jnp ref arm and the Pallas megakernel (identical jnp
    ops on the same int words -> bitwise-identical geometry and topology
    across formats).  ``words`` is a sequence of the row's int32 words,
    each an array of one common shape (the ref passes (w,) columns of a
    row gather, the kernel (1, C) lane rows); ``pcode`` is the frontier's
    carried parent-code lane (u8 format only — the row stores just the
    child's octant).

    Returns ``(xyz, full, child_start, child_mask, code_own)`` where
    ``xyz`` is the three int32 cell-coordinate arrays at ``level`` and
    ``code_own`` the lane's own Morton code (int32; only meaningful —
    and only used — under ``meta_format="u8"``, where children inherit
    it as their ``pcode``).
    """
    w0 = words[0]
    zero = jnp.zeros_like(w0)
    if meta_format == "fp32":
        code = w0
        xyz = [_jnp_compact1by2(code >> k).astype(jnp.int32)
               for k in range(3)]
        return xyz, words[1] != 0, words[2], words[3], zero
    # Topology word: full << 31 | [octant << 28 |] child_start << 8 | mask.
    # w0 >> k is an arithmetic shift (sign-extends when full is set); the
    # field masks strip the extension bits.
    full_l = w0 < 0
    child_mask = w0 & 0xFF
    if meta_format == "bf16":
        child_start = (w0 >> 8) & ((1 << BF16_START_BITS) - 1)
        w1 = words[1]
        # Geometry word: 3 x 10-bit leaf-grid coords; a level-l cell
        # coordinate is the field shifted back down (exact by packing).
        shift = jnp.int32(GRID_BITS) - level
        xyz = [((w1 >> 20) & 0x3FF) >> shift,
               ((w1 >> 10) & 0x3FF) >> shift,
               (w1 & 0x3FF) >> shift]
        return xyz, full_l, child_start, child_mask, zero
    assert meta_format == "u8" and pcode is not None, \
        f"unknown meta_format {meta_format!r}; allowed: {META_FORMATS}"
    child_start = (w0 >> 8) & ((1 << U8_START_BITS) - 1)
    code_own = (pcode << 3) | ((w0 >> 28) & 7)
    xyz = [_jnp_compact1by2(code_own >> k).astype(jnp.int32)
           for k in range(3)]
    return xyz, full_l, child_start, child_mask, code_own


def _empty_stats():
    return dict(
        nodes=jnp.int32(0), leaf=jnp.int32(0), axis_exec=jnp.int32(0),
        axis_dec=jnp.int32(0), sphere=jnp.int32(0), overflow=jnp.int32(0),
        per_level=jnp.zeros((MAX_DEPTH + 1,), jnp.int32),
        exit_hist=jnp.zeros((NUM_EXIT_CODES,), jnp.int32),
        meta_rows=jnp.int32(0), meta_gathers=jnp.int32(0))


def gather_pairs(tile: jax.Array, win: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Distinct (tile, chunk, window) keys among the valid lanes of one
    level: the megakernel's gather products there.  Valid lanes form the
    prefix and are sorted by tile, so a lane's chunk is its position past
    its tile's first lane over :data:`CHUNK`."""
    w = tile.shape[0]
    i = jnp.arange(w, dtype=jnp.int32)
    first = valid & ((i == 0) | (tile != jnp.roll(tile, 1)))
    start = jax.lax.cummax(jnp.where(first, i, 0))
    # A monotone id per (tile, chunk); invalid lanes sort last.
    tc = jnp.where(valid, jnp.cumsum(first | ((i - start) % CHUNK == 0)),
                   w + 1)
    tc, win = jax.lax.sort((tc, jnp.where(valid, win, 0)), num_keys=2)
    new = (i == 0) | (tc != jnp.roll(tc, 1)) | (win != jnp.roll(win, 1))
    return jnp.sum(jnp.where(new & (tc <= w), 1, 0))


def traverse_whole_ref(obb_c, obb_h, obb_r, node_meta, cell_sizes, scene_lo,
                       depth: int, capacity: int, use_spheres: bool,
                       scene_of_query: Optional[jax.Array] = None,
                       w_min: int = 128, owner_of_query=None, payload=None,
                       tile_bq: Optional[int] = None,
                       stream_wsub: Optional[int] = None,
                       scene_off: Optional[jax.Array] = None,
                       scene_counts: Optional[jax.Array] = None,
                       scene_of_tile: Optional[jax.Array] = None,
                       num_valid=None, valid_of_query=None,
                       meta_format: str = "fp32",
                       codes: Optional[jax.Array] = None):
    """Whole-traversal reference arm; see module docstring for the contract.

    Args:
      node_meta: (depth+1, n_max, words) int32 packed CSR metadata rows
        (fp32: [code, full, child_start, child_mask]; bf16/u8: the
        compressed layouts of :mod:`repro.core.quantize`); single-scene
        ``DeviceOctree.node_meta`` or the flat ``MultiSceneOctree`` table.
      meta_format: row encoding of ``node_meta`` ("fp32" | "bf16" | "u8");
        must match the packing the table was built with.  Under "u8" the
        row stores only the node's octant: the kernel carries an extra
        own-Morton-code frontier lane, while this ref gathers the same
        bits from ``codes`` (required then) — the retained
        ``DeviceOctree.codes`` plane.
      cell_sizes: (depth+1,) f32, or (S, depth+1) when ragged.
      scene_lo: (3,) f32, or (S, 3) when ragged.
      scene_of_query: (Q,) int32 scene id per flat query, or None for a
        single scene.
      owner_of_query / payload: optional verdict-group and payload lanes
        (:mod:`repro.engine.plan`): the verdict becomes the (Q,) int32
        per-group ``best`` payload that hit (``PAYLOAD_INF`` = never;
        owner ids are compact so cells past the group count are unused),
        and a pair expands only while its payload could still beat its
        group's best — boolean early exit is the identity-owner,
        zero-payload special case.
      tile_bq: the kernel's query-tile width; given, the ``meta_gathers``
        stat models the kernel's gather products (module docstring),
        else it stays 0.
      stream_wsub / scene_off / scene_counts / scene_of_tile (with
        ``tile_bq``): model the megakernel's streamed metadata layout (see
        module docstring): ``stream_wsub`` is the fixed sub-level window
        size in rows,
        ``scene_off`` / ``scene_counts`` the (S, depth+1) per-scene flat
        sub-extents of the level rows (S = 1 and offset 0 for a single
        scene), and ``scene_of_tile`` the (num_tiles,) scene id of each
        query tile.  The ``meta_rows`` stat then counts the row-exact
        spans the per-(tile, window) schedule fetches; without them it
        stays 0 (resident layout).
      num_valid: optional live-prefix query count (int, possibly traced):
        only slots ``[0, num_valid)`` of the pool seed the frontier; the
        tail is padding that contributes ZERO work to any counter.  The
        sharded executor pads every shard's pool to a common width and
        passes each shard's true count here, which is what makes sharded
        counters bitwise-equal to single-device (``None`` = all Q slots
        are live).
      valid_of_query: optional (Q,) bool mask of live pool slots for
        tiled (owner-group / ragged) pools, whose pads sit at each
        TILE's tail rather than the pool's.  Live slots seed the
        frontier in ascending slot order; masked slots contribute zero
        work, exactly like the ``num_valid`` tail.  Mutually exclusive
        with ``num_valid``.
    Returns:
      (verdict, stats dict) — the ``_traverse_fused`` contract: (Q,) bool
      collide flags, or the (Q,) ``best`` array for grouped calls.
    """
    Q = obb_c.shape[0]
    n_max = node_meta.shape[-2]
    assert meta_format != "u8" or codes is not None, \
        "u8 rows need the codes plane to reconstruct lane geometry"
    ragged = scene_of_query is not None
    grouped = owner_of_query is not None or payload is not None
    model_stream = stream_wsub is not None
    if model_stream:
        assert scene_off is not None and scene_counts is not None \
            and scene_of_tile is not None and tile_bq is not None, \
            "streamed-window model needs the full (bq, wsub, extents) spec"
        num_tiles = -(-Q // tile_bq)
        num_wins = -(-n_max // stream_wsub)   # static window grid per level
    else:
        num_tiles = num_wins = 0
    widths = frontier_widths(capacity, w_min)
    widths_arr = jnp.asarray(widths, jnp.int32)

    def make_branch(w: int):
        lane_w = jnp.arange(w, dtype=jnp.int32)

        def branch(level, n_live, q_idx, node_idx, verdict, st):
            q = q_idx[:w]
            idx = node_idx[:w]
            idx_c = jnp.clip(idx, 0, n_max - 1)
            valid = lane_w < n_live
            meta_row = jax.lax.dynamic_index_in_dim(node_meta, level,
                                                    keepdims=False)
            meta = meta_row[idx_c]                              # (w, words)
            if meta_format == "u8":
                # The kernel carries an own-Morton-code frontier lane (it
                # cannot reach the codes plane under streaming); the ref
                # gathers the lane's code from the retained plane instead —
                # same bits ((pcode << 3) | octant reconstructs the gathered
                # code exactly), no capacity-sized carry or scatter.
                pcode = (jax.lax.dynamic_index_in_dim(
                    codes, level, keepdims=False)[idx_c].astype(jnp.int32)
                    >> 3)
            else:
                pcode = None
            xyz, full_l, child_start, child_mask, code_own = \
                decode_meta_words([meta[:, k] for k in range(meta.shape[1])],
                                  meta_format, level, pcode)
            xyz = jnp.stack(xyz, axis=-1)
            is_leaf = level == depth

            if ragged:
                sid = scene_of_query[q]
                cell = jax.lax.dynamic_index_in_dim(
                    cell_sizes, level, axis=1, keepdims=False)[sid]   # (w,)
                lo = scene_lo[sid]                                    # (w, 3)
            else:
                cell = jax.lax.dynamic_index_in_dim(cell_sizes, level,
                                                    keepdims=False)
                lo = scene_lo
            node_c, node_h = node_centers_from_xyz(xyz, lo, cell)
            res = sact_mod.sact_frontier_staged(
                obb_c[q], obb_h[q], obb_r[q], node_c, node_h, valid,
                use_spheres=use_spheres)
            is_term = jnp.where(is_leaf, True, full_l)
            overlap = res.collide & valid
            term_hit = overlap & is_term
            if grouped:
                pay = (jnp.zeros(q.shape, jnp.int32) if payload is None
                       else payload[q])
                own = q if owner_of_query is None else owner_of_query[q]
                verdict = payload_min_update(verdict, own, pay, term_hit)
                undecided = pay < verdict[own]
            else:
                verdict = verdict.at[q].max(term_hit)
                undecided = ~verdict[q]

            # ---- work accounting (formulas of the fused arm, bitwise) ----
            n_valid = jnp.sum(valid.astype(jnp.int32))
            term_valid = (valid & is_term).astype(jnp.int32)

            # ---- in-register CSR expansion (see module docstring) --------
            expand = overlap & ~is_term & undecided
            occupied, offs = csr_child_slots(child_mask)
            n_child = jnp.where(expand,
                                jax.lax.population_count(child_mask), 0)
            base = jnp.cumsum(n_child) - n_child                  # (w,)
            n_new = jnp.sum(n_child)
            live = expand[:, None] & occupied
            tgt = jnp.where(live, base[:, None] + offs,
                            capacity).reshape(-1)
            q_next = jnp.zeros((capacity,), jnp.int32).at[tgt].set(
                jnp.repeat(q, 8), mode="drop")
            idx_next = jnp.zeros((capacity,), jnp.int32).at[tgt].set(
                (child_start[:, None] + offs).reshape(-1), mode="drop")

            # ---- streamed-window schedule model (kernel-identical) -------
            if model_stream:
                # A kernel tile fetches window w of ITS scene's sub-extent
                # at this level iff some valid lane of the tile points into
                # it; the fetched span is the window's occupied extent
                # rounded out to whole META_ROW_ALIGN-row DMA chunks.
                off_l = jax.lax.dynamic_index_in_dim(
                    scene_off, level, axis=1, keepdims=False)       # (S,)
                cnt_l = jax.lax.dynamic_index_in_dim(
                    scene_counts, level, axis=1, keepdims=False)    # (S,)
                off_lane = off_l[sid] if ragged else off_l[0]
                win_g = (idx - off_lane) // stream_wsub
                win = jnp.clip(win_g, 0, num_wins - 1)
                live = jnp.zeros((num_tiles, num_wins), jnp.int32).at[
                    q // tile_bq, win].max(valid.astype(jnp.int32),
                                           mode="drop")
                off_t = off_l[scene_of_tile][:, None]       # (T, 1)
                cnt_t = cnt_l[scene_of_tile][:, None]
                wlo = jnp.arange(num_wins, dtype=jnp.int32)[None, :] \
                    * stream_wsub                           # (1, NW)
                occ = jnp.clip(cnt_t - wlo, 0, stream_wsub)
                g_lo = off_t + wlo
                g_hi = g_lo + occ
                a = META_ROW_ALIGN
                span = jnp.where(occ > 0,
                                 (-(-g_hi // a)) * a - (g_lo // a) * a, 0)
                meta_rows = st["meta_rows"] + jnp.sum(live * span)
            else:
                meta_rows = st["meta_rows"]
                win_g = idx // RESIDENT_WINDOW
            gathers = st["meta_gathers"]
            if tile_bq is not None:
                gathers = gathers + gather_pairs(q // tile_bq, win_g, valid)

            st = dict(
                nodes=st["nodes"] + n_valid,
                leaf=st["leaf"] + jnp.sum(term_valid),
                axis_exec=st["axis_exec"] + jnp.sum(res.axis_tests),
                axis_dec=st["axis_dec"] + n_valid * NUM_AXES,
                sphere=st["sphere"] + jnp.sum(res.sphere_tests),
                overflow=st["overflow"] + jnp.maximum(n_new - capacity, 0),
                per_level=st["per_level"].at[level].set(n_valid),
                exit_hist=st["exit_hist"].at[res.exit_code].add(term_valid),
                meta_rows=meta_rows, meta_gathers=gathers)
            return (level + 1, jnp.minimum(n_new, capacity), q_next,
                    idx_next, verdict, st)
        return branch

    branches = [make_branch(w) for w in widths]

    def body(carry):
        n_live = carry[1]
        k = jnp.sum((widths_arr < n_live).astype(jnp.int32))
        return jax.lax.switch(k, branches, *carry)

    def cond(carry):
        level, n_live = carry[0], carry[1]
        return (level <= depth) & (n_live > 0)

    lane = jnp.arange(capacity, dtype=jnp.int32)
    if valid_of_query is not None:
        assert num_valid is None, \
            "valid_of_query and num_valid are mutually exclusive"
        # Tiled pools pad at each TILE's tail: compact the live slots (in
        # ascending slot order, preserving the tile-contiguous layout the
        # window model keys on) into the frontier prefix.
        (q0,) = jnp.nonzero(valid_of_query, size=capacity, fill_value=0)
        q0 = q0.astype(jnp.int32)
        n0 = jnp.sum(valid_of_query.astype(jnp.int32))
    else:
        q0 = jnp.where(lane < Q, lane, 0)
        n0 = jnp.asarray(Q if num_valid is None else num_valid, jnp.int32)
    if ragged:
        # scene s's root sits at flat index s of the level-0 row.
        node0 = scene_of_query[q0].astype(jnp.int32)
    else:
        node0 = jnp.zeros((capacity,), jnp.int32)
    verdict0 = (jnp.full((Q,), PAYLOAD_INF, jnp.int32) if grouped
                else jnp.zeros((Q,), bool))
    carry0 = (jnp.int32(0), jnp.minimum(n0, jnp.int32(capacity)),
              q0, node0, verdict0, _empty_stats())
    out = jax.lax.while_loop(cond, body, carry0)
    return out[4], out[5]
