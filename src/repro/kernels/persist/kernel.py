"""Persistent whole-traversal Pallas megakernel — one ``pallas_call`` for
the ENTIRE multi-level wavefront walk.

RoboGPU's central claim (§II, Fig. 11) is that a collision query should
stay *resident in the core* across the whole tree walk: conditional
returns, never spilling intermediates.  The per-level fused step
(:mod:`repro.kernels.traverse`) still launches one kernel per octree level
and round-trips the compacted frontier through HBM between levels; this
kernel removes that last HBM round trip.  The grid walks tiles of ``bq``
pool slots, and each grid step owns its tile's traversal end to end.

**Layout.**  Every per-lane quantity is a ``(1, CHUNK)`` lane row, and the
level loop runs inside the kernel body.  The tile's frontier lives in a
VMEM scratch ``(8, fcap)`` whose rows ``3s .. 3s+2`` hold slot ``s``'s
(query, CSR node index, parent code) lanes — level ``l`` reads slot
``l % 2`` and writes its children to the other slot, so the frontier
never exists in HBM.  A second ``(16, fcap)`` scratch stashes each lane's
gathered metadata words and expansion state between the passes of a
level.  Only the live prefix ``[0, n_live)`` is visited, ``CHUNK`` lanes
at a time; a drained frontier makes the remaining levels no-ops.

Each level makes four passes over its live chunks:

  1. **gather** — node-metadata rows are gathered window by window (a
     TPU core has no vector gather from a large table) by a one-hot
     matmul on the MXU over byte planes: the window's words, lane-dense
     by sheet, against a one-hot of each lane's row within its sheet;
     each lane then takes its own sheet's bytes;
  2. **test** — decode the words in-register
     (:func:`repro.kernels.persist.ref.decode_meta_words`, shared with the
     ref arm), gather the lanes' query OBBs, payloads and owners from the
     tile's ``bq`` slots by one one-hot matmul on the MXU over byte
     planes, run the two-phase staged SACT via the
     shared :func:`repro.kernels.sact.kernel.sact_tile`, fold terminal hits
     into the tile's per-group ``best`` column and count the work;
  3. **scan** — a lane expands iff it overlaps a non-terminal node and its
     payload can still beat its group's best after the level's folds; its
     child count (popcount of the CSR occupancy mask) is prefix-summed
     over the level;
  4. **expand** — each next-frontier chunk finds its parents by range test
     against the parents' [base, base + count) child ranges: child ``k`` of
     parent ``i`` lands at ``base[i] + k`` and is node ``child_start[i] +
     k`` — the order of the ref arm's scatter, without a scatter; the
     parent's query, start and code reach the child by a one-hot matmul
     on the MXU over byte planes.

The one-hot matmuls (:func:`onehot_gather`) are exact: every int32 word —
an OBB field's f32 bits, bitcast — is split into its four bytes, which
bf16 holds exactly (0..255); a one-hot column has at most a single 1, so
each f32 accumulator adds one byte and zeros; the bytes are joined back
bitwise, sign bit included.  Every bit pattern round-trips, negative zero,
denormals and NaN payloads too.  Pass 1 counts its matmuls into the
``gathers`` scalar (``Counters.meta_gathers``), which the ref arm models.

Children past ``fcap`` are dropped and counted; the count lands in
``Counters.frontier_overflow`` and the engine's escalate-on-overflow
policy replays the query set at a larger capacity.  Verdicts are exact iff
the overflow count is zero.

**Owner-group tiling.**  The host packs the pool so every verdict group
(all pairs sharing an ``owner_of_query`` — e.g. the segment lanes of one
swept CCD edge) lands in ONE tile (:func:`repro.kernels.persist.ops.
build_tile_map`).  The per-tile ``owner_local`` input names each slot's
group by the group's first slot in the tile (``-1`` = pad slot; live slots
form each tile's prefix).  The payload min-fold and its early-exit gate
run on the group one-hot, so one segment's first hit retires its sibling
lanes in-kernel.  Identity owners (``owner_local = slot``) reproduce the
per-query boolean/payload kernel bit-for-bit.

**Ragged multi-scene batches** run on the same flat CSR table
(:class:`repro.core.octree.MultiSceneOctree`): tiles are scene-exclusive,
the per-tile ``scene_of_tile`` id picks the scene's origin/cell-size row
of the flat ``scal`` table and its rows of the per-scene level sub-extent
tables, and the tile's frontier seeds at the scene's root (flat node index
``s`` of the level-0 row).

Node metadata reaches the kernel as ``(depth+1, words, rows/128, 128)``
int32 — each level's words as lane-dense sheets — in one of two
**layouts** (``stream``) x three row **formats** (``meta_fmt``: fp32 = 16
B, bf16 = 8 B, u8 = 4 B rows — :mod:`repro.core.quantize`):

* ``resident`` — the whole table is one single-buffered VMEM block, read in
  aligned :data:`RESIDENT_WINDOW`-row windows;
* ``streamed`` — the table stays in HBM (``pltpu.ANY``) and each level is
  iterated through fixed-size sub-level windows of ``wsub`` rows over the
  tile's scene sub-extent, double-buffered through a ping/pong VMEM pair:
  while window ``w`` is gathered from one slot, the DMA for the tile's
  NEXT live window is already in flight into the other (windows no lane
  points into are skipped).  A window's fetched span is its occupied
  extent rounded out to whole :data:`repro.core.octree.META_ROW_ALIGN`-row
  sheets; rows fetched are counted into the ``meta_rows`` scalar, and the
  jnp ref arm models the identical per-(tile, window) schedule.

On clean (overflow-free) runs the union of per-tile work is bitwise the
work of the global-frontier ref arm: same pairs per level, same exit
codes, same counters.  Overflow accounting is per tile, so each backend
escalates against its own overflow count until clean.

On the CPU test matrix the kernel (both layouts, including the DMA window
machinery) runs under ``interpret=True`` on small scenes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.counters import NUM_EXIT_CODES
from repro.core.octree import META_ROW_ALIGN
from repro.core.quantize import META_FORMAT_WORDS
from repro.core.sact import PAYLOAD_INF, axis_tests_from_exit
from repro.kernels.persist.ref import CHUNK, RESIDENT_WINDOW, decode_meta_words
# _EPS shared with every SACT arm: the bitwise identity across engines
# depends on all of them using the same epsilon and op order.
from repro.kernels.sact.kernel import _EPS, NUM_AXES, sact_tile

#: Per-tile stats column: per-level valid counts, exit histogram, scalars.
STATS_ROWS = 48
_HIST0, _SCAL0 = 16, 40
_HIST_ROWS = _SCAL0 - _HIST0
assert NUM_EXIT_CODES <= _HIST_ROWS
#: Scalars, in order, at rows ``_SCAL0 ..`` of the stats column.
STAT_SCALARS = ("nodes", "leaf", "axis_exec", "axis_dec", "sphere",
                "overflow", "meta_rows", "gathers")
assert _SCAL0 + len(STAT_SCALARS) <= STATS_ROWS

# SMEM counter slots (per-level counts occupy [0, 16)).
_LEAF, _AXIS, _OVF, _ROWS, _GATH = 16, 17, 18, 19, 20
# Stash scratch rows.  Rows 8..15 form one aligned group that the expand
# pass reads as one (8, CHUNK) block of parent lanes.
_W0, _PAY, _OWN, _MASK = 0, 4, 5, 6
_BASE, _NCH, _START, _CODE, _Q = 8, 9, 10, 11, 12
#: Rows of the per-tile query operand: 15 OBB words, payload, owner, pad.
_QROWS = 24


def byte_planes(vals: jax.Array) -> jax.Array:
    """(R, K) int32 -> (4R, K) bf16 left operand of :func:`onehot_gather`:
    row ``b * R + r`` holds byte ``b`` of ``vals[r]`` (0..255, exact in
    bf16)."""
    planes = [(vals >> (8 * b)) & 0xFF for b in range(4)]
    return jnp.concatenate(planes, axis=0).astype(jnp.float32).astype(
        jnp.bfloat16)


def onehot_gather(planes: jax.Array, onehot: jax.Array, *, nseg: int = 1,
                  sheet: jax.Array | None = None,
                  rhs_t: bool = False) -> jax.Array:
    """Exact per-lane picks of int32 values under a one-hot, on the MXU.

    ``planes`` is :func:`byte_planes` of ``(R * nseg, K)`` values — with
    ``nseg`` > 1, ``R`` values of ``nseg`` segments each, segment-minor.
    ``onehot`` is ``(K, C)`` bool (``(C, K)`` with ``rhs_t``): lane ``c``
    picks row ``k`` where it is set, nothing where its column is all
    false.  One bf16 matmul with an f32 accumulator picks every byte at
    once: each output is a byte times 1 plus zeros, exact.  With ``nseg``
    > 1, lane ``c`` then takes segment ``sheet[0, c]`` of each value (none
    if out of range).  The bytes are joined bitwise, so every int32 —
    every f32 bit pattern, bitcast — comes back as it went in.  Returns
    ``(R, C)`` int32; columns that picked nothing read 0.
    """
    hot = jnp.where(onehot, 1.0, 0.0).astype(jnp.bfloat16)
    dims = (((1,), (1,)), ((), ())) if rhs_t else (((1,), (0,)), ((), ()))
    prod = jax.lax.dot_general(planes, hot, dims,
                               preferred_element_type=jnp.float32)
    n = prod.shape[0] // (4 * nseg)
    if nseg > 1:
        hit = jax.lax.broadcasted_iota(jnp.int32, (nseg, prod.shape[1]),
                                       0) == sheet
        prod = jnp.concatenate(
            [jnp.sum(jnp.where(hit, prod[i * nseg:(i + 1) * nseg], 0.0),
                     axis=0, keepdims=True) for i in range(4 * n)], axis=0)
    b = prod.astype(jnp.int32)
    return (b[:n] | (b[n:2 * n] << 8) | (b[2 * n:3 * n] << 16)
            | (b[3 * n:] << 24))


def persist_kernel(scal_ref, off_ref, cnt_ref, sot_ref, nvalid_ref, obb_ref,
                   lane_ref, meta_ref, best_ref, stats_ref, fr_scr, st_scr,
                   qp_scr, best_scr, hist_scr, cnt_smem, cb_smem,
                   *win_scratch,
                   bq: int, fcap: int, depth: int, n_rows: int,
                   use_spheres: bool, stream: bool, meta_fmt: str,
                   wsub: int):
    C = CHUNK
    L = depth + 1
    vpf = META_FORMAT_WORDS[meta_fmt]
    u8 = meta_fmt == "u8"
    t = pl.program_id(0)
    q_base = t * bq
    s = sot_ref[t]                      # this tile's scene id
    sb = s * (3 + L)                    # this scene's row of the flat scal
    inf = jnp.int32(PAYLOAD_INF)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)

    def rows_at(ref, row, c):
        return ref[pl.ds(row, 1), pl.ds(pl.multiple_of(c * C, C), C)]

    def put(ref, row, c, val):
        ref[pl.ds(row, 1), pl.ds(pl.multiple_of(c * C, C), C)] = val

    # ---- per-tile init + seed frontier (slot 0) ------------------------
    # Live-prefix mask: live slots form each tile's prefix (the tile map
    # pads at tile tails) AND sit before the SMEM valid count (the sharded
    # executor's pool-tail pads) — a fully padded tile seeds nothing.
    own_col = lane_ref[:, 1:2]
    n_q = jnp.minimum(jnp.sum(jnp.where(own_col >= 0, 1, 0)),
                      jnp.clip(nvalid_ref[0] - q_base, 0, bq))
    best_scr[...] = jnp.full((bq, 1), inf, jnp.int32)
    hist_scr[...] = jnp.zeros((_HIST_ROWS, 1), jnp.int32)
    for i in range(_GATH + 1):
        cnt_smem[i] = jnp.int32(0)
    # Pass 2's left operand, once per tile: the slots' words lane-dense —
    # rows 0..14 the OBB's f32 bits, 15 payload, 16 owner.
    qp_scr[...] = byte_planes(jnp.concatenate(
        [jax.lax.bitcast_convert_type(obb_ref[...], jnp.int32).T,
         lane_ref[...].T, jnp.zeros((_QROWS - 17, bq), jnp.int32)], axis=0))
    for c in range(-(-bq // C)):
        lane = c * C + iota_c
        seed = lane < n_q
        put(fr_scr, 0, c, jnp.where(seed, q_base + lane, 0))
        put(fr_scr, 1, c, jnp.where(seed, s, 0))       # scene s's root
        put(fr_scr, 2, c, jnp.zeros((1, C), jnp.int32))

    if stream:
        meta_scr, dma_sem = win_scratch
        lg = wsub.bit_length() - 1
        nseg = wsub // META_ROW_ALIGN + 1     # sheets a window can span
    else:
        lg = RESIDENT_WINDOW.bit_length() - 1
        nseg = RESIDENT_WINDOW // META_ROW_ALIGN
    nw = -(-n_rows // (1 << lg))              # static window-index bound
    nw_pad = -(-nw // 8) * 8

    def level_body(level, n_live):
        slot = jax.lax.rem(level, 2)
        nxt = 1 - slot
        n_chunks = (n_live + C - 1) // C
        cell = scal_ref[sb + 3 + level]
        lo = [scal_ref[sb + i] for i in range(3)]
        if stream:
            off_l = off_ref[s * L + level]
            cnt_l = cnt_ref[s * L + level]
        else:
            off_l = jnp.int32(0)

        def win_of(idx):
            return (idx - off_l) >> lg

        # ---- pass 1: gather metadata words, window-major -------------
        iota_w = jax.lax.broadcasted_iota(jnp.int32, (nw_pad, 1), 0)

        def occ_body(c, occ):
            valid = c * C + iota_c < n_live
            w_row = jnp.where(valid, win_of(rows_at(fr_scr, 3 * slot + 1, c)),
                              -1)
            hit = jnp.where(w_row == jax.lax.broadcasted_iota(
                jnp.int32, (nw_pad, C), 0), 1, 0)
            return jnp.maximum(occ, jnp.max(hit, axis=1, keepdims=True))
        occ = jax.lax.fori_loop(0, n_chunks, occ_body,
                                jnp.zeros((nw_pad, 1), jnp.int32))

        def next_window(w):
            return jnp.min(jnp.where((iota_w > w) & (occ > 0), iota_w, nw))

        if stream:
            def window_dma(op, w, ws):
                """Start or wait the row-sheet DMAs of window ``w`` into
                ping/pong slot ``ws``; returns the rows fetched."""
                g_lo = off_l + w * wsub
                occ_rows = jnp.clip(cnt_l - w * wsub, 0, wsub)
                r0 = g_lo // META_ROW_ALIGN
                n_sheets = ((g_lo + occ_rows + META_ROW_ALIGN - 1)
                            // META_ROW_ALIGN - r0)

                def sheet(k, carry):
                    dma = pltpu.make_async_copy(
                        meta_ref.at[level, :, pl.ds(r0 + k, 1), :],
                        meta_scr.at[ws, :, pl.ds(k, 1), :],
                        dma_sem.at[ws])
                    (dma.start if op == "start" else dma.wait)()
                    return carry
                jax.lax.fori_loop(0, n_sheets, sheet, 0)
                return n_sheets * META_ROW_ALIGN

            w_first = next_window(-1)

            @pl.when(w_first < nw)
            def _():
                window_dma("start", w_first, 0)

        iota_row = jax.lax.broadcasted_iota(jnp.int32, (META_ROW_ALIGN, C), 0)

        def win_body(w, k):
            has_w = jnp.sum(jnp.where(iota_w == w, occ, 0)) > 0

            @pl.when(has_w)
            def _():
                if stream:
                    ks = jax.lax.rem(k, 2)
                    rows = window_dma("wait", w, ks)
                    nx = next_window(w)

                    @pl.when(nx < nw)
                    def _():
                        window_dma("start", nx, 1 - ks)

                    cnt_smem[_ROWS] = cnt_smem[_ROWS] + rows
                    sheet_lo = ((off_l + w * wsub) // META_ROW_ALIGN
                                * META_ROW_ALIGN)
                    sheets = meta_scr[ks]
                else:
                    sheet_lo = w * RESIDENT_WINDOW
                    sheets = meta_ref[level, :,
                                      pl.ds(pl.multiple_of(w * nseg, 8), nseg),
                                      :]
                # The window's words by sheet, lane-dense: (vpf * gseg, 128).
                gseg = sheets.shape[1]
                planes = byte_planes(sheets.reshape(vpf * gseg, META_ROW_ALIGN))

                def chunk_body(c, carry):
                    valid = c * C + iota_c < n_live
                    idx = rows_at(fr_scr, 3 * slot + 1, c)
                    in_w = valid & (win_of(idx) == w)

                    @pl.when(jnp.sum(jnp.where(in_w, 1, 0)) > 0)
                    def _():
                        # Row within the sheet on the MXU, then the sheet.
                        local = idx - sheet_lo
                        words = onehot_gather(
                            planes, (local & (META_ROW_ALIGN - 1)) == iota_row,
                            nseg=gseg, sheet=local >> 7)
                        for kk in range(vpf):
                            put(st_scr, _W0 + kk, c,
                                jnp.where(in_w, words[kk:kk + 1],
                                          rows_at(st_scr, _W0 + kk, c)))
                        cnt_smem[_GATH] = cnt_smem[_GATH] + 1
                    return carry
                jax.lax.fori_loop(0, n_chunks, chunk_body, 0)
            return k + jnp.where(has_w, 1, 0)
        jax.lax.fori_loop(0, nw, win_body, jnp.int32(0))

        # ---- pass 2: decode + OBB gather + staged SACT + fold ----------
        iota_q = jax.lax.broadcasted_iota(jnp.int32, (bq, C), 0)
        iota_e = jax.lax.broadcasted_iota(jnp.int32, (_HIST_ROWS, C), 0)

        def test_body(c, n_valid):
            valid = c * C + iota_c < n_live
            q = rows_at(fr_scr, 3 * slot, c)
            pcode = rows_at(fr_scr, 3 * slot + 2, c) if u8 else None
            words = [rows_at(st_scr, _W0 + kk, c) for kk in range(vpf)]
            xyz, full_l, child_start, child_mask, code_own = \
                decode_meta_words(words, meta_fmt, level, pcode)
            qw = onehot_gather(qp_scr[...], (q - q_base) == iota_q)
            f = [jax.lax.bitcast_convert_type(qw[i:i + 1], jnp.float32)
                 for i in range(15)]
            pay, own = qw[15:16], qw[16:17]
            node_c = [lo[i] + (xyz[i].astype(jnp.float32) + 0.5) * cell
                      for i in range(3)]
            tt = [f[i] - node_c[i] for i in range(3)]
            R = [[f[6 + 3 * i + k] for k in range(3)] for i in range(3)]
            A = [[jnp.abs(R[i][k]) + _EPS for k in range(3)]
                 for i in range(3)]
            collide, exit_code = sact_tile(tt, R, A, [cell * 0.5] * 3,
                                           f[3:6], use_spheres=use_spheres)
            is_term = full_l | (level == depth)
            overlap = collide & valid
            term_hit = overlap & is_term
            # Terminal hits fold the lane's payload into its GROUP's best.
            o_hot = own == iota_q
            best_scr[...] = jnp.minimum(best_scr[...], jnp.min(
                jnp.where(o_hot & term_hit, pay, inf), axis=1,
                keepdims=True))
            term_valid = valid & is_term
            cnt_smem[_LEAF] = cnt_smem[_LEAF] + jnp.sum(
                jnp.where(term_valid, 1, 0))
            cnt_smem[_AXIS] = cnt_smem[_AXIS] + jnp.sum(
                jnp.where(valid, axis_tests_from_exit(exit_code), 0))
            hist_scr[...] = hist_scr[...] + jnp.sum(
                jnp.where((exit_code == iota_e) & term_valid, 1, 0), axis=1,
                keepdims=True)
            put(st_scr, _PAY, c, pay)
            put(st_scr, _OWN, c, own)
            # Expansion candidates: a zero mask == not a candidate (a
            # non-full internal node has at least one occupied child).
            put(st_scr, _MASK, c, jnp.where(overlap & ~is_term, child_mask, 0))
            put(st_scr, _START, c, child_start)
            put(st_scr, _CODE, c, code_own)
            put(st_scr, _Q, c, q)
            return n_valid + jnp.sum(jnp.where(valid, 1, 0))
        n_valid = jax.lax.fori_loop(0, n_chunks, test_body, jnp.int32(0))
        cnt_smem[level] = n_valid

        # ---- pass 3: group-best gate + child-count prefix sum ---------
        tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))

        def scan_body(c, base):
            own = rows_at(st_scr, _OWN, c)
            mask = rows_at(st_scr, _MASK, c)
            best_lane = jnp.min(jnp.where(own == iota_q, best_scr[...], inf),
                                axis=0, keepdims=True)
            expand = (mask != 0) & (rows_at(st_scr, _PAY, c) < best_lane)
            n_child = jnp.where(expand, jax.lax.population_count(mask), 0)
            n_col = jnp.broadcast_to(n_child, (8, C)).T[:, 0:1]     # (C, 1)
            incl = jnp.sum(jnp.where(tri, n_col, 0), axis=0, keepdims=True)
            put(st_scr, _BASE, c, base + incl - n_child)
            put(st_scr, _NCH, c, n_child)
            cb_smem[c] = base
            return base + jnp.sum(n_child)
        n_new = jax.lax.fori_loop(0, n_chunks, scan_body, jnp.int32(0))
        cb_smem[n_chunks] = n_new
        n_next = jnp.minimum(n_new, fcap)
        cnt_smem[_OVF] = cnt_smem[_OVF] + jnp.maximum(n_new - fcap, 0)

        # ---- pass 4: place children into the other frontier slot ------
        iota_cc = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)

        def out_body(o, carry):
            p = o * C + iota_c
            p_col = o * C + iota_cc                 # child position, sublanes

            def parent_body(c, acc):
                hit = (cb_smem[c] < (o + 1) * C) & (cb_smem[c + 1] > o * C)

                def add(acc):
                    par = st_scr[pl.ds(_BASE, 8),
                                 pl.ds(pl.multiple_of(c * C, C), C)]
                    b = par[0:1]
                    owns_t = (b <= p_col) & (p_col < b + par[1:2])  # (C, C)
                    vals = jnp.concatenate(
                        [par[4:5], par[2:3] - b, par[3:4],
                         jnp.zeros((5, C), jnp.int32)], axis=0)
                    return acc + onehot_gather(byte_planes(vals), owns_t,
                                               rhs_t=True)
                return jax.lax.cond(hit, add, lambda a: a, acc)
            got = jax.lax.fori_loop(0, n_chunks, parent_body,
                                    jnp.zeros((8, C), jnp.int32))
            q_n, rel_n, code_n = got[0:1], got[1:2], got[2:3]
            live = p < n_next
            put(fr_scr, 3 * nxt, o, jnp.where(live, q_n, 0))
            put(fr_scr, 3 * nxt + 1, o, jnp.where(live, rel_n + p, 0))
            # Children inherit this lane's own code as their pcode (u8).
            put(fr_scr, 3 * nxt + 2, o, jnp.where(live, code_n, 0))
            return carry
        jax.lax.fori_loop(0, (n_next + C - 1) // C, out_body, 0)
        return n_next

    jax.lax.fori_loop(0, L, level_body, jnp.minimum(n_q, fcap))

    # ---- outputs ---------------------------------------------------------
    best_ref[...] = best_scr[...]
    iota16 = jax.lax.broadcasted_iota(jnp.int32, (16, 1), 0)
    per_level = jnp.zeros((16, 1), jnp.int32)
    nodes = jnp.int32(0)
    for lv in range(L):
        per_level = jnp.where(iota16 == lv, cnt_smem[lv], per_level)
        nodes = nodes + cnt_smem[lv]
    stats_ref[0:_HIST0, :] = per_level
    stats_ref[_HIST0:_SCAL0, :] = hist_scr[...]
    vals = (nodes, cnt_smem[_LEAF], cnt_smem[_AXIS], nodes * NUM_AXES,
            2 * nodes if use_spheres else jnp.int32(0), cnt_smem[_OVF],
            cnt_smem[_ROWS], cnt_smem[_GATH])
    iota8 = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    scal = jnp.zeros((8, 1), jnp.int32)
    for i, v in enumerate(vals):
        scal = jnp.where(iota8 == i, v, scal)
    stats_ref[_SCAL0:STATS_ROWS, :] = scal


def vmem_scratch_bytes(bq: int, fcap: int, vpf: int, stream: bool,
                       wsub: int) -> int:
    """VMEM bytes of the kernel's scratch (frontier, stash, accumulators,
    streamed window pair), excluding the resident table and temporaries."""
    fpad = _frontier_lanes(bq, fcap)
    total = ((8 + 16) * fpad * 4 + 4 * _QROWS * bq * 2 + bq * 128 * 4
             + _HIST_ROWS * 128 * 4)
    if stream:
        total += 2 * vpf * _window_sheets(wsub) * 128 * 4
    return total


def _frontier_lanes(bq: int, fcap: int) -> int:
    return -(-max(fcap, bq) // CHUNK) * CHUNK


def _window_sheets(wsub: int) -> int:
    return -(-(wsub // META_ROW_ALIGN + 1) // 8) * 8


def make_persist_call(num_tiles: int, bq: int, fcap: int, depth: int,
                      n_rows: int, use_spheres: bool, interpret: bool,
                      stream: bool, meta_fmt: str = "fp32",
                      wsub: int = 1024, vmem_limit_bytes: int | None = None):
    """Build the whole-traversal pallas_call.

    Inputs: scal (S * (3 + depth+1),) f32 SMEM — per scene [scene_lo xyz,
    per-level cells], flat scene-major; scene_off / scene_counts
    (S * (depth+1),) int32 SMEM — per-scene flat sub-extents of the level
    rows; scene_of_tile (num_tiles,) int32 SMEM; live query count (1,)
    int32 SMEM; OBB table (num_tiles * bq, 15) f32, blocked per tile;
    lanes (num_tiles * bq, 2) int32 [payload, owner_local] per slot
    (owner_local = the slot's verdict group as the group's first
    tile-local slot, ``-1`` = pad); node_meta (depth+1, words, n_rows/128,
    128) int32 packed per ``meta_fmt`` — a single-buffered VMEM block, or
    an HBM-space (``pltpu.ANY``) table streamed through the ping/pong
    window scratch when ``stream``.  Outputs: ``best`` (num_tiles * bq, 1)
    int32 per owner slot (``PAYLOAD_INF`` = that group never hit; 0 = a
    boolean hit) and the (num_tiles * STATS_ROWS, 1) int32 stats column
    per tile (see :data:`STATS_ROWS`).
    """
    assert bq % 8 == 0, "query tiles are whole 8-row OBB blocks"
    assert n_rows % RESIDENT_WINDOW == 0, "tables are whole 1024-row windows"
    if stream:
        assert wsub & (wsub - 1) == 0 and wsub >= META_ROW_ALIGN, wsub
    L = depth + 1
    vpf = META_FORMAT_WORDS[meta_fmt]
    fpad = _frontier_lanes(bq, fcap)
    kernel = functools.partial(
        persist_kernel, bq=bq, fcap=fcap, depth=depth, n_rows=n_rows,
        use_spheres=use_spheres, stream=stream, meta_fmt=meta_fmt,
        wsub=wsub)
    sheets = n_rows // META_ROW_ALIGN
    meta_spec = (pl.BlockSpec(memory_space=pl.ANY) if stream
                 else pl.BlockSpec((L, vpf, sheets, META_ROW_ALIGN),
                                   lambda t: (0, 0, 0, 0),
                                   pipeline_mode=pl.Buffered(1)))
    scratch = [
        pltpu.VMEM((8, fpad), jnp.int32),          # frontier, 2 slots x 3
        pltpu.VMEM((16, fpad), jnp.int32),         # per-lane stash
        pltpu.VMEM((4 * _QROWS, bq), jnp.bfloat16),    # query byte planes
        pltpu.VMEM((bq, 1), jnp.int32),            # per-group best
        pltpu.VMEM((_HIST_ROWS, 1), jnp.int32),    # exit histogram
        pltpu.SMEM((32,), jnp.int32),              # scalar counters
        pltpu.SMEM((fpad // CHUNK + 1,), jnp.int32),   # chunk child bases
    ]
    if stream:
        scratch += [
            pltpu.VMEM((2, vpf, _window_sheets(wsub), META_ROW_ALIGN),
                       jnp.int32),                 # window ping/pong pair
            pltpu.SemaphoreType.DMA((2,)),
        ]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(num_tiles,),
        in_specs=[
            smem, smem, smem, smem, smem,      # scal, off, counts, sot, nv
            pl.BlockSpec((bq, 15), lambda t: (t, 0)),
            pl.BlockSpec((bq, 2), lambda t: (t, 0)),
            meta_spec,
        ],
        out_specs=[
            pl.BlockSpec((bq, 1), lambda t: (t, 0)),
            pl.BlockSpec((STATS_ROWS, 1), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles * bq, 1), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles * STATS_ROWS, 1), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="persist_traverse",
    )
