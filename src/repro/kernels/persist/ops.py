"""Dispatch + glue for the persistent whole-traversal megakernel.

``traverse_whole`` is the single entry point of ``mode=
"wavefront_persistent"``: the ENTIRE multi-level traversal in one call —
the Pallas megakernel on TPU (or ``interpret=True`` for the CPU CI
matrix), the live-prefix jnp reference elsewhere.  Both arms share the
contract of :func:`repro.engine.executor._traverse_fused` — identical
``(collide, stats)`` including every work counter — so the engine's
escalation policy and counter plumbing are mode-agnostic.

**Every plan shape runs on the kernel arm.**  Plans whose pairs cannot be
tiled per-query — cross-slot owner groups (swept-edge CCD) and ragged
multi-scene batches — are lowered to a **tiled pool** first
(:func:`build_tile_map`): pool slots are permuted so every verdict group
lands in one ``bq``-slot tile, tiles never mix scenes, and pads sit at
each tile's tail.  Both arms then consume the SAME permuted pool (the ref
via a slot validity mask), so verdicts and all work counters stay
bitwise-comparable; outputs are mapped back to query/group space in-graph.
The only capability fallback left is an owner group too large for the
largest tile (:data:`MAX_TILE_BQ`; :func:`persist_kernel_unsupported`
names it so the executor can count and log the downgrade).

**Metadata residency layouts x row formats.**  The megakernel holds node
metadata in one of two layouts (:data:`META_LAYOUTS`, DESIGN.md §3):

* ``resident`` — the whole ``(depth+1, n_max, words)`` table is a VMEM
  block (:func:`meta_table_bytes`); fastest when it fits.
* ``streamed`` — the table stays in HBM and each level is iterated
  through fixed-size sub-level windows of :func:`sub_window_rows` rows,
  double-buffered through a ping/pong VMEM scratch pair
  (:func:`meta_stream_bytes` resident bytes — constant in ``n_max``); the
  row-exact fetched spans are counted into the ``meta_rows`` stat →
  ``Counters.meta_rows_streamed`` → priced at the format's row width.

Rows come in one of three formats (:data:`repro.core.quantize.META_FORMATS`:
fp32 = 16 B, bf16 = 8 B, u8 = 4 B — see :mod:`repro.core.quantize` for the
encodings and the soundness argument).  The format is a property of the
packed :class:`DeviceOctree` / :class:`MultiSceneOctree`
(``dev.meta_format``); both arms decode it in-register and
verdicts/counters are bitwise format-independent.

``traverse_whole(streamed=None)`` picks the layout with
:func:`choose_meta_layout` against :data:`DEFAULT_VMEM_BUDGET` (pinning
the tree's own format); the engine's executor runs the full
layout x format chooser per (mode, statics) traversal cache key and
passes both down explicitly (``EngineConfig.stream_meta`` /
``meta_format`` / ``vmem_budget`` override it).  Ragged multi-scene
tables stream and compress exactly like single scenes — the per-scene
sub-extents (``MultiSceneOctree.scene_off`` / ``scene_counts``) key each
tile's window schedule to its own scene.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.counters import (BYTES_META_STREAM, BYTES_META_STREAM_BF16,
                                 BYTES_META_STREAM_U8, NUM_EXIT_CODES)
from repro.core.octree import (MAX_DEPTH, META_ROW_ALIGN, DeviceOctree,
                               MultiSceneOctree, align_rows)
from repro.core.quantize import META_FORMAT_WORDS, META_FORMATS, format_eligible
from repro.core.sact import PAYLOAD_INF
from repro.kernels.persist.kernel import (_HIST0, _SCAL0, RESIDENT_WINDOW,
                                          STAT_SCALARS, STATS_ROWS,
                                          _window_sheets, make_persist_call,
                                          vmem_scratch_bytes)
from repro.kernels.persist.ref import traverse_whole_ref
from repro.kernels.sact.ops import pack_obbs

#: Node-metadata layouts of the persistent megakernel (drift-guarded
#: against the DESIGN.md §3 / README residency tables).
META_LAYOUTS = ("resident", "streamed")

#: Bytes per node-metadata row ([code, full, child_start, child_mask],
#: 4 x int32) — the unit of the residency estimates, aliased to the
#: traffic model's ``BYTES_META_STREAM`` so the two can never drift.
META_BYTES_PER_ROW = BYTES_META_STREAM

#: Bytes per packed row by format, aliased to the traffic-model constants
#: (:mod:`repro.core.quantize` defines the encodings; fp32 = 4 int32
#: words, bf16 = 2, u8 = 1).
META_FORMAT_BYTES = {"fp32": BYTES_META_STREAM,
                     "bf16": BYTES_META_STREAM_BF16,
                     "u8": BYTES_META_STREAM_U8}

#: Fixed sub-level window size in rows for the streamed layout: each
#: level is iterated ``wsub`` rows at a time, so the VMEM window scratch
#: is constant in ``n_max`` (a level narrower than this streams in one
#: window).
SUB_WINDOW_ROWS = 1024

#: Largest owner-group tile the megakernel will build.  A verdict group
#: must fit in one tile (its fold cell is tile-local), so a plan whose
#: largest owner group exceeds this many pairs is a genuine capability
#: fallback to the ref arm (:func:`persist_kernel_unsupported`).
MAX_TILE_BQ = 1024

#: Largest per-tile frontier (lanes) the megakernel holds in VMEM.  The
#: engine's frontier capacity is a global bound; a tile never needs more
#: than its own share, and past this cap a tile's children overflow (and
#: are counted) instead of growing the scratch.
MAX_TILE_FRONTIER = 65536

#: Highest scoped-VMEM limit a megakernel call asks for: a TPU v5e
#: TensorCore's 128 MiB of VMEM less headroom for the compiler's own
#: internal scratch.
KERNEL_VMEM_CAP = 100 * 1024 * 1024

#: Compiler temporaries of the megakernel body (one-hot operands and
#: products of the MXU gathers and the (CHUNK, CHUNK) scan/placement
#: planes) allowed on top of its declared scratch.  The v5e compile of the
#: largest tile (MAX_TILE_BQ queries, MAX_TILE_FRONTIER lanes) over a
#: depth-7 resident fp32 table of the whole DEFAULT_VMEM_BUDGET needs
#: 1.25-1.5 MiB on top of table and scratch (it fails at a 1.25 MiB
#: allowance, passes at 1.5 MiB; ``tests/test_tpu_compile.py`` compiles
#: that corner); this allows ten times that.
KERNEL_TEMP_BYTES = 16 * 1024 * 1024

#: Default VMEM budget for the resident node-metadata table: what the
#: capped scoped limit leaves after the largest tile's scratch and the
#: compiler's temporaries.  ``EngineConfig.vmem_budget`` overrides per
#: engine; CPU/interpret runs have no hard limit but honor the same
#: estimate so layout choice is backend-independent.
DEFAULT_VMEM_BUDGET = (KERNEL_VMEM_CAP - KERNEL_TEMP_BYTES
                       - vmem_scratch_bytes(MAX_TILE_BQ, MAX_TILE_FRONTIER, 4,
                                            True, SUB_WINDOW_ROWS))


def meta_table_bytes(depth: int, n_max: int, fmt: str = "fp32") -> int:
    """VMEM bytes of the RESIDENT node-metadata table: the kernel's
    single-buffered block, rows padded to whole 1024-row windows."""
    rows = -(-max(int(n_max), 1) // RESIDENT_WINDOW) * RESIDENT_WINDOW
    return (depth + 1) * rows * META_FORMAT_BYTES[fmt]


def sub_window_rows(n_max: int) -> int:
    """Streamed sub-level window size in rows for an ``n_max``-wide table
    (the fixed :data:`SUB_WINDOW_ROWS`, shrunk to the power of two that
    covers the aligned table when the whole table is narrower)."""
    return min(SUB_WINDOW_ROWS, _next_pow2(align_rows(n_max)))


def meta_stream_bytes(n_max: int, fmt: str = "fp32") -> int:
    """VMEM bytes of the STREAMED layout's ping/pong window pair.

    Each slot holds the 128-row sheets one fixed-size sub-level window
    can span (its occupied extent rounds OUT to whole sheets, so an
    unaligned window touches one sheet more).  Constant in ``n_max`` once
    the table is wider than :data:`SUB_WINDOW_ROWS`: VMEM scratch is fully
    decoupled from the widest level, so arbitrarily large scenes stream
    through the same budget.
    """
    return (2 * META_FORMAT_WORDS[fmt] * _window_sheets(sub_window_rows(n_max))
            * META_ROW_ALIGN * 4)


class MetaChoice(NamedTuple):
    """A point in the {resident, streamed} x {fp32, bf16, u8} plan space."""
    layout: str
    fmt: str


def choose_meta_layout(depth: int, n_max: int,
                       budget: int = DEFAULT_VMEM_BUDGET,
                       fmt: Optional[str] = None,
                       layout: Optional[str] = None) -> MetaChoice:
    """Layout/format chooser over {resident, streamed} x {fp32, bf16, u8}.

    ``fmt`` / ``layout`` pin one or both axes (``None`` = free).  Rules:

    * **Format preference runs widest-first for residency** (fp32 > bf16 >
      u8): compression is only taken when it buys residency the wider
      format cannot afford — a table that fits in fp32 stays fp32 (zero
      decode cost, no reason to compress).
    * **Streamed rows are narrowest-first** (u8 > bf16 > fp32): once the
      table streams, row width is pure HBM traffic, so the narrowest
      *eligible* format wins.
    * **Eligibility** (:func:`repro.core.quantize.format_eligible`) caps
      compressed formats by their CSR ``child_start`` field width (bf16:
      23 bits, u8: 20); fp32 is always eligible.

    Pinning an ineligible ``fmt`` raises ``ValueError`` (a packed table
    with overflowed pointers cannot exist); a free search only visits
    eligible formats, so the fallback is always sound.
    """
    if fmt is not None and fmt not in META_FORMATS:
        raise ValueError(f"unknown meta_format {fmt!r}; "
                         f"allowed: {META_FORMATS}")
    if layout is not None and layout not in META_LAYOUTS:
        raise ValueError(f"unknown meta layout {layout!r}; "
                         f"allowed: {META_LAYOUTS}")
    if fmt is not None and not format_eligible(fmt, n_max):
        raise ValueError(
            f"meta_format {fmt!r} cannot index {n_max} rows per level "
            "(CSR child_start field overflow)")
    widest = [f for f in META_FORMATS if format_eligible(f, n_max)]
    narrowest = widest[::-1]
    if fmt is not None:
        if layout is None:
            layout = ("resident"
                      if meta_table_bytes(depth, n_max, fmt) <= budget
                      else "streamed")
        return MetaChoice(layout, fmt)
    if layout == "resident":
        for f in widest:
            if meta_table_bytes(depth, n_max, f) <= budget:
                return MetaChoice("resident", f)
        return MetaChoice("resident", "fp32")   # nothing fits; pinned anyway
    if layout == "streamed":
        return MetaChoice("streamed", narrowest[0])
    for f in widest:
        if meta_table_bytes(depth, n_max, f) <= budget:
            return MetaChoice("resident", f)
    return MetaChoice("streamed", narrowest[0])


def _use_pallas_default() -> bool:
    return jax.default_backend() == "tpu"


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class Tiling(NamedTuple):
    """Traced-array view of a tiled pool (crosses jit; see TileMap).

    The pool has ``num_tiles * bq`` slots; all four arrays are int32.
    """
    owner_local: jax.Array    # (Q',) slot's verdict group as the group's
    #                           first tile-local slot; -1 = pad slot
    scene_of_tile: jax.Array  # (T,) scene id per tile (0 = single scene)
    slot_of_query: jax.Array  # (Q,) original query -> pool slot
    group_slot: jax.Array     # (Q,) global group id -> the group's fold
    #                           slot; -1 past the group count


class TileMap(NamedTuple):
    """Host-side owner-group tiling of a plan's pair pool.

    ``perm[slot]`` is the original query index occupying the slot (-1 =
    pad); callers permute their per-query arrays with ``np.maximum(perm,
    0)`` (pad slots carry garbage rows, masked by ``owner_local < 0``).
    """
    tiles: Tiling             # numpy-backed Tiling arrays
    perm: np.ndarray          # (Q',) int64
    bq: int
    num_tiles: int


def build_tile_map(num_queries: int, bq: int,
                   scene_of_query: Optional[np.ndarray] = None,
                   owner_of_query: Optional[np.ndarray] = None,
                   max_bq: int = MAX_TILE_BQ) -> TileMap:
    """Pack a plan's pairs into scene-exclusive, owner-group-exclusive
    tiles (host-side numpy; runs once per plan shape).

    Pairs are ordered scene-major / owner-minor (stable, so real pools —
    already sorted this way by the front ends — keep their order), and
    each (scene, owner) run is placed whole into the first tile of its
    scene with room, opening a new tile on scene change or overflow.
    ``bq`` grows to the next power of two that fits the largest group
    (capped at ``max_bq``: a larger group raises — the executor screens
    with :func:`persist_kernel_unsupported` first).  Pads sit at each
    tile's TAIL, so live slots form every tile's prefix.
    """
    Q = int(num_queries)
    soq = (np.zeros(Q, np.int64) if scene_of_query is None
           else np.asarray(scene_of_query, np.int64))
    own = (np.arange(Q, dtype=np.int64) if owner_of_query is None
           else np.asarray(owner_of_query, np.int64))
    assert soq.shape == (Q,) and own.shape == (Q,)
    order = np.lexsort((own, soq))
    so, oo = soq[order], own[order]
    new_run = np.ones(Q, bool)
    if Q > 1:
        new_run[1:] = (so[1:] != so[:-1]) | (oo[1:] != oo[:-1])
    run_id = np.cumsum(new_run) - 1
    run_starts = np.flatnonzero(new_run)
    run_sizes = np.diff(np.append(run_starts, Q))
    run_owner = oo[run_starts]
    if owner_of_query is not None and \
            len(np.unique(run_owner)) != len(run_owner):
        raise ValueError("an owner group spans multiple scenes; "
                         "its fold cell cannot be tile-local")
    max_run = int(run_sizes.max()) if Q else 1
    bq_eff = max(int(bq), _next_pow2(max_run))
    if bq_eff > max_bq:
        raise ValueError(
            f"owner group of {max_run} pairs needs a {bq_eff}-slot tile "
            f"(cap {max_bq}); screen with persist_kernel_unsupported")

    nrun = len(run_starts)
    tile_of_run = np.zeros(nrun, np.int64)
    first_slot_of_run = np.zeros(nrun, np.int64)
    run_scene = so[run_starts] if nrun else np.zeros(0, np.int64)
    scene_of_tile = []
    tile, used, cur_scene = -1, bq_eff, None
    for r in range(nrun):
        n = int(run_sizes[r])
        s = int(run_scene[r])
        if s != cur_scene or used + n > bq_eff:
            tile += 1
            used = 0
            cur_scene = s
            scene_of_tile.append(s)
        tile_of_run[r] = tile
        first_slot_of_run[r] = used
        used += n
    num_tiles = max(tile + 1, 1)
    if not scene_of_tile:
        scene_of_tile = [0]

    rank_in_run = np.arange(Q) - run_starts[run_id] if Q else np.zeros(0)
    slot_sorted = (tile_of_run[run_id] * bq_eff + first_slot_of_run[run_id]
                   + rank_in_run).astype(np.int64)
    slot_of_query = np.zeros(Q, np.int64)
    slot_of_query[order] = slot_sorted
    Qs = num_tiles * bq_eff
    perm = np.full(Qs, -1, np.int64)
    perm[slot_sorted] = order
    owner_local = np.full(Qs, -1, np.int32)
    owner_local[slot_sorted] = first_slot_of_run[run_id].astype(np.int32)
    group_slot = np.full(Q, -1, np.int32)
    if nrun:
        group_slot[run_owner] = (tile_of_run * bq_eff
                                 + first_slot_of_run).astype(np.int32)
    tiles = Tiling(owner_local=owner_local,
                   scene_of_tile=np.asarray(scene_of_tile, np.int32),
                   slot_of_query=slot_of_query.astype(np.int32),
                   group_slot=group_slot)
    return TileMap(tiles=tiles, perm=perm, bq=bq_eff, num_tiles=num_tiles)


def persist_kernel_unsupported(owner_of_query=None, scene_of_query=None,
                               max_bq: int = MAX_TILE_BQ) -> Optional[str]:
    """Name the reason a persistent-mode plan cannot run on the kernel
    arm, or ``None`` if it can.

    After owner-group tiling there are exactly two capability limits
    left: an owner group too large for the largest tile, and an owner
    group spanning scenes (no front end emits one).  The executor calls
    this before tiling so a downgrade is counted
    (``Counters.ref_arm_fallbacks``) and logged, never silent.
    """
    if owner_of_query is None:
        return None
    own = np.asarray(owner_of_query)
    if own.size == 0:
        return None
    sizes = np.bincount(own.astype(np.int64))
    mx = int(sizes.max())
    if _next_pow2(mx) > max_bq:
        return (f"owner group of {mx} pairs needs a {_next_pow2(mx)}-slot "
                f"tile (cap {max_bq})")
    if scene_of_query is not None:
        soq = np.asarray(scene_of_query)
        pairs = {(int(o), int(s)) for o, s in zip(own, soq)}
        if len(pairs) != len(np.unique(own)):
            return "an owner group spans multiple scenes"
    return None


def _scene_extents(dev) -> Tuple[jax.Array, jax.Array]:
    """(S, depth+1) per-scene flat level sub-extents (offset, count)."""
    L = dev.depth + 1
    if isinstance(dev, MultiSceneOctree):
        return (dev.scene_off.astype(jnp.int32),
                dev.scene_counts.astype(jnp.int32))
    return (jnp.zeros((1, L), jnp.int32),
            jnp.reshape(dev.counts.astype(jnp.int32), (1, L)))


def _kernel_meta_table(node_meta: jax.Array) -> jax.Array:
    """(depth+1, n_max, words) packed rows -> the megakernel's
    (depth+1, words, n_rows/128, 128) lane-dense sheets, rows padded to
    whole :data:`repro.kernels.persist.kernel.RESIDENT_WINDOW` windows."""
    L, n_max, vpf = node_meta.shape
    n_rows = -(-n_max // RESIDENT_WINDOW) * RESIDENT_WINDOW
    meta = jnp.pad(node_meta, ((0, 0), (0, n_rows - n_max), (0, 0)))
    return jnp.transpose(meta, (0, 2, 1)).reshape(
        L, vpf, n_rows // META_ROW_ALIGN, META_ROW_ALIGN)


def kernel_vmem_limit(depth: int, n_max: int, fmt: str, stream: bool,
                      bq: int, fcap: int) -> int:
    """Scoped-VMEM limit for one megakernel call: its scratch, the
    resident table (single-buffered) and :data:`KERNEL_TEMP_BYTES` of
    compiler temporaries, capped at :data:`KERNEL_VMEM_CAP`."""
    need = (vmem_scratch_bytes(bq, fcap, META_FORMAT_WORDS[fmt], stream,
                               sub_window_rows(n_max))
            + KERNEL_TEMP_BYTES)
    if not stream:
        need += meta_table_bytes(depth, n_max, fmt)
    return min(need, KERNEL_VMEM_CAP)


def _kernel_whole(obb_c, obb_h, obb_r, dev, capacity: int,
                  use_spheres: bool, bq: int, interpret: bool, stream: bool,
                  payload=None, num_valid=None, owner_local=None,
                  scene_of_tile=None) -> Tuple[jax.Array, dict]:
    """Run the megakernel; returns the RAW (num_tiles * bq,) per-slot
    ``best`` words (PAYLOAD_INF = that owner slot never hit) + stats."""
    M = obb_c.shape[0]
    L = dev.depth + 1
    n_max = dev.node_meta.shape[-2]
    fmt = getattr(dev, "meta_format", "fp32")
    obb = pack_obbs(obb_c, obb_h, obb_r)
    pay = (jnp.zeros((M,), jnp.int32) if payload is None
           else payload.astype(jnp.int32))
    if owner_local is not None:
        num_tiles = scene_of_tile.shape[0]
        bq = M // num_tiles
        assert num_tiles * bq == M, "tiled pools are exact tile multiples"
        own = owner_local.astype(jnp.int32)
        sot = scene_of_tile.astype(jnp.int32)
    else:
        num_tiles = max(math.ceil(M / bq), 1)
        pad = num_tiles * bq - M
        obb = jnp.pad(obb, ((0, pad), (0, 0)))
        pay = jnp.pad(pay, (0, pad))
        # Identity owners: every slot its own verdict group; validity
        # comes from the SMEM live-prefix count alone.
        own = jnp.tile(jnp.arange(bq, dtype=jnp.int32), num_tiles)
        sot = jnp.zeros((num_tiles,), jnp.int32)
    if isinstance(dev, MultiSceneOctree):
        scal = jnp.concatenate(
            [dev.scene_lo, dev.cell_sizes], axis=1
        ).astype(jnp.float32).reshape(-1)
    else:
        scal = jnp.concatenate([jnp.asarray(dev.scene_lo, jnp.float32),
                                jnp.asarray(dev.cell_sizes, jnp.float32)])
    off, cnt = _scene_extents(dev)
    meta = _kernel_meta_table(dev.node_meta)
    nvalid = jnp.reshape(jnp.asarray(M if num_valid is None else num_valid,
                                     jnp.int32), (1,))
    fcap = min(capacity, MAX_TILE_FRONTIER)
    call = make_persist_call(
        num_tiles, bq, fcap, dev.depth, meta.shape[2] * META_ROW_ALIGN,
        use_spheres, interpret, stream, meta_fmt=fmt,
        wsub=sub_window_rows(n_max),
        vmem_limit_bytes=kernel_vmem_limit(dev.depth, n_max, fmt, stream,
                                           bq, fcap))
    best, stats = call(scal, off.reshape(-1), cnt.reshape(-1), sot, nvalid,
                       obb, jnp.stack([pay, own], axis=1), meta)
    tot = jnp.sum(stats.reshape(num_tiles, STATS_ROWS), axis=0)
    sc = dict(zip(STAT_SCALARS, tot[_SCAL0:_SCAL0 + len(STAT_SCALARS)]))
    per = jnp.zeros((MAX_DEPTH + 1,), jnp.int32).at[:L].set(tot[:L])
    st = dict(nodes=sc["nodes"], leaf=sc["leaf"], axis_exec=sc["axis_exec"],
              axis_dec=sc["axis_dec"], sphere=sc["sphere"],
              overflow=sc["overflow"], per_level=per,
              exit_hist=tot[_HIST0:_HIST0 + NUM_EXIT_CODES],
              meta_rows=sc["meta_rows"], meta_gathers=sc["gathers"])
    return best.reshape(-1), st


def traverse_whole(obb_c, obb_h, obb_r, dev, capacity: int, *,
                   use_spheres: bool, use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None,
                   scene_of_query: Optional[jax.Array] = None,
                   owner_of_query: Optional[jax.Array] = None,
                   payload: Optional[jax.Array] = None,
                   streamed: Optional[bool] = None,
                   bq: int = 128, w_min: int = 128,
                   num_valid=None,
                   tiles: Optional[Tiling] = None) -> Tuple[jax.Array, dict]:
    """Whole multi-level traversal for one flat query set.

    ``dev`` is a single-scene :class:`DeviceOctree`, or a
    :class:`MultiSceneOctree` with ``scene_of_query`` (Q,) mapping each
    flat query to its scene.  Composes under jit; returns
    ``(collide (Q,) bool, stats dict)`` bitwise-identical to the per-level
    fused arm.

    ``streamed`` selects the node-metadata layout (see module docstring):
    ``None`` asks :func:`choose_meta_layout` with the default budget.  The
    layout cannot change verdicts or work counters — only the ``meta_rows``
    stat (HBM window traffic, 0 under the resident layout) and the VMEM
    footprint move.  Both kernel and ref arms honor it, so kernel-vs-ref
    runs stay bitwise-comparable per layout, for every plan shape
    (ragged and owner-tiled included).

    Payload lanes (:mod:`repro.engine.plan`): with owner / payload lanes
    the verdict is the (Q,) int32 ``best`` payload per verdict group
    (compact owner ids; cells past the group count are ``PAYLOAD_INF``).
    Cross-slot owner groups and ragged multi-scene pools are lowered to
    an owner-group tiled pool (:func:`build_tile_map`) and run on the
    SAME arm machinery as identity plans: when such a plan arrives
    untiled (and eager — tiling needs concrete ids; the executor
    pre-tiles before jit), the tile map is built here, the pool permuted
    into slot space, and outputs mapped back.  ``tiles`` given means the
    caller already permuted ``obb_* / owner_of_query / payload`` into
    slot space; outputs still come back in query/group space
    (``slot_of_query`` / ``group_slot`` are carried by ``tiles``).

    ``num_valid`` (traced int32, default all Q) marks the live prefix of
    the pool: slots at and past it never seed the frontier and contribute
    ZERO work to every counter, so a padded pool traverses bitwise like
    its unpadded prefix.  The sharded executor pads every shard's local
    pool to a common width and passes the true per-shard count.
    """
    ragged = isinstance(dev, MultiSceneOctree)
    assert ragged or scene_of_query is None, \
        "scene_of_query needs a MultiSceneOctree flat table"
    obb_c = jnp.asarray(obb_c)
    obb_h = jnp.asarray(obb_h)
    obb_r = jnp.asarray(obb_r)
    fmt = getattr(dev, "meta_format", "fp32")
    n_max = dev.node_meta.shape[-2]
    if streamed is None:
        streamed = choose_meta_layout(
            dev.depth, n_max, fmt=fmt).layout == "streamed"
    if use_pallas is None:
        use_pallas = _use_pallas_default()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    grouped = owner_of_query is not None or payload is not None

    if tiles is None and (ragged or owner_of_query is not None):
        if any(isinstance(x, jax.core.Tracer)
               for x in (scene_of_query, owner_of_query)):
            # The tile map needs concrete ids; the executor pre-tiles for
            # the kernel arm before jit, so a traced untiled call is the
            # per-level modes' legacy ref routing (resident model only).
            assert not use_pallas, \
                "the kernel arm needs a pre-built tile map under jit"
            return traverse_whole_ref(
                obb_c, obb_h, obb_r, dev.node_meta, dev.cell_sizes,
                dev.scene_lo, dev.depth, capacity, use_spheres,
                scene_of_query=scene_of_query, w_min=w_min,
                owner_of_query=owner_of_query, payload=payload,
                num_valid=num_valid, meta_format=fmt,
                codes=getattr(dev, "codes", None))
        # Untiled non-identity plan: build the tile map eagerly (needs
        # concrete scene/owner ids — the executor pre-tiles before jit)
        # and re-enter in slot space.
        assert not ragged or scene_of_query is not None, \
            "a MultiSceneOctree needs scene_of_query (Q,) untiled"
        reason = persist_kernel_unsupported(
            None if owner_of_query is None else np.asarray(owner_of_query),
            None if scene_of_query is None else np.asarray(scene_of_query))
        if reason is not None:
            # Capability gap: the ref arm serves the plan untiled (the
            # executor counts and logs this routing).
            assert not use_pallas, f"kernel arm unsupported: {reason}"
            return traverse_whole_ref(
                obb_c, obb_h, obb_r, dev.node_meta, dev.cell_sizes,
                dev.scene_lo, dev.depth, capacity, use_spheres,
                scene_of_query=scene_of_query, w_min=w_min,
                owner_of_query=owner_of_query, payload=payload,
                num_valid=num_valid, meta_format=fmt,
                codes=getattr(dev, "codes", None))
        tm = build_tile_map(
            obb_c.shape[0], bq,
            None if scene_of_query is None else np.asarray(scene_of_query),
            None if owner_of_query is None else np.asarray(owner_of_query))
        perm = np.maximum(tm.perm, 0)
        return traverse_whole(
            jnp.asarray(obb_c)[perm], jnp.asarray(obb_h)[perm],
            jnp.asarray(obb_r)[perm], dev, capacity,
            use_spheres=use_spheres, use_pallas=use_pallas,
            interpret=interpret,
            owner_of_query=(None if owner_of_query is None
                            else jnp.asarray(owner_of_query)[perm]),
            payload=(None if payload is None
                     else jnp.asarray(payload)[perm]),
            streamed=streamed, bq=tm.bq, w_min=w_min,
            tiles=jax.tree.map(jnp.asarray, tm.tiles))

    if tiles is not None:
        Qs = obb_c.shape[0]
        num_tiles = tiles.scene_of_tile.shape[0]
        bq_t = Qs // num_tiles
        assert num_tiles * bq_t == Qs, "tiled pools are exact multiples"
        Q = tiles.slot_of_query.shape[0]
        valid = tiles.owner_local >= 0
        if use_pallas:
            best, st = _kernel_whole(
                obb_c, obb_h, obb_r, dev, capacity, use_spheres, bq_t,
                interpret, stream=streamed, payload=payload,
                owner_local=tiles.owner_local,
                scene_of_tile=tiles.scene_of_tile)
        else:
            off, cnt = _scene_extents(dev)
            soq_slot = (jnp.repeat(tiles.scene_of_tile, bq_t) if ragged
                        else None)
            best, st = traverse_whole_ref(
                obb_c, obb_h, obb_r, dev.node_meta, dev.cell_sizes,
                dev.scene_lo, dev.depth, capacity, use_spheres,
                scene_of_query=soq_slot, w_min=w_min,
                owner_of_query=owner_of_query, payload=payload,
                tile_bq=bq_t,
                stream_wsub=sub_window_rows(n_max) if streamed else None,
                scene_off=off if streamed else None,
                scene_counts=cnt if streamed else None,
                scene_of_tile=tiles.scene_of_tile if streamed else None,
                valid_of_query=valid, meta_format=fmt,
                codes=getattr(dev, "codes", None))
        if grouped:
            if use_pallas:
                # Kernel bests live at each group's fold slot; the ref's
                # live at the global group id.  Cells past the group
                # count are PAYLOAD_INF either way.
                out = jnp.where(
                    tiles.group_slot >= 0,
                    best[jnp.clip(tiles.group_slot, 0, Qs - 1)],
                    jnp.int32(PAYLOAD_INF))
            else:
                out = best[:Q]
        else:
            slot_best = (best != PAYLOAD_INF) if use_pallas else best
            out = slot_best[tiles.slot_of_query]
        return out, st

    # ---- identity (single-scene, per-query groups) pools --------------
    M = obb_c.shape[0]
    if use_pallas:
        best, st = _kernel_whole(obb_c, obb_h, obb_r, dev, capacity,
                                 use_spheres, bq, interpret,
                                 stream=streamed, payload=payload,
                                 num_valid=num_valid)
        best = best[:M]
        return (best if grouped else best != PAYLOAD_INF), st
    off, cnt = _scene_extents(dev)
    return traverse_whole_ref(obb_c, obb_h, obb_r, dev.node_meta,
                              dev.cell_sizes, dev.scene_lo, dev.depth,
                              capacity, use_spheres,
                              scene_of_query=None, w_min=w_min,
                              owner_of_query=None, payload=payload,
                              tile_bq=bq,
                              stream_wsub=(sub_window_rows(n_max)
                                           if streamed else None),
                              scene_off=off if streamed else None,
                              scene_counts=cnt if streamed else None,
                              scene_of_tile=(
                                  jnp.zeros((max(math.ceil(M / bq), 1),),
                                            jnp.int32)
                                  if streamed else None),
                              num_valid=num_valid,
                              meta_format=fmt,
                              codes=getattr(dev, "codes", None))
