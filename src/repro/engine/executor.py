"""Plan executor: one engine consuming :class:`repro.engine.plan.QueryPlan`.

DESIGN — plan/execute split
===========================
The front-end shapes the repo serves (single query set, (B, M) batch,
ragged multi-scene, trajectory, swept edge) all lower to one canonical
flat pool — see :mod:`repro.engine.plan`.  This module owns everything
downstream of the lowering, for every plan alike:

  * **mode dispatch** — the paper's Fig. 11 arms (``EngineConfig.mode``,
    DESIGN.md §2): host-loop ablations, the device-resident wavefront
    ``lax.while_loop``, the fused per-level traversal step
    (:mod:`repro.kernels.traverse`), and the persistent whole-traversal
    megakernel (:mod:`repro.kernels.persist`);
  * **the traversal cache** — one jit-compiled traversal per (mode, batch
    kind, capacity, statics), LRU-keyed so repeated engines and
    escalation replays never retrace (:func:`traversal_cache_info`);
  * **capacity escalation** — the frontier runs in a fixed-capacity
    buffer; overflow is counted on device and the query replays at 4x
    capacity until clean (see the README's capacity policy);
  * **counter assembly** — device-side stats become
    :class:`repro.core.counters.Counters`, including the §4 bytes model.

Profiler spans (``jax.profiler.TraceAnnotation``, recorded only while a
profiler trace is active) mark the executor's phases on the host plane,
which shares its clock with the device trace: ``engine.execute`` around a
call, ``executor.stage`` (the plan's host arrays placed on the device),
``executor.dispatch`` (one jitted traversal call per escalation rung) and
``executor.sync`` (each blocking device-to-host read: ``overflow``,
``counters``, ``verdict``).  They add no sync and no device work.

Verdict state generalizes from a boolean per query to an int32 ``best``
per *verdict group* (``PAYLOAD_INF`` = undecided): a terminal hit folds
the pair's payload lane in with a min, and a pair expands only while its
payload could still beat its group's best — which is exactly the boolean
early exit when every slot owns itself and every payload is zero, and
per-edge first-hit with in-traversal early exit for swept-edge plans.
Boolean plans keep the original boolean code path, so verdicts and work
counters of all pre-existing modes are bitwise-identical to the
pre-split engine (CI-enforced).

``core/wavefront.py`` remains as a compatibility shim re-exporting this
module's public names.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import time
import weakref
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import sact as sact_mod
from repro.core.counters import (BYTES_FUSED_STEP, BYTES_FUSED_TEST,
                                 BYTES_META_STREAM, BYTES_META_STREAM_BF16,
                                 BYTES_META_STREAM_U8, BYTES_PAYLOAD_LANE,
                                 BYTES_PERSIST_QUERY, BYTES_SHADER_HANDOFF,
                                 BYTES_UNFUSED_TEST, NUM_EXIT_CODES,
                                 Counters)
from repro.core.geometry import OBBs
from repro.core.octree import (MAX_DEPTH, DeviceOctree, Octree,
                               concat_device_octrees, device_octree,
                               lookup_children, node_centers_from_codes,
                               stack_device_octrees)
from repro.core.quantize import META_FORMATS
from repro.core.sact import (NUM_AXES, PAYLOAD_INF, SactResult,
                             payload_min_update)
from repro.engine.plan import QueryPlan, plan_batch, plan_queries, plan_scenes
from repro.kernels.compact.ops import compact_pairs
from repro.kernels.persist import ops as persist_ops
from repro.kernels.persist.ops import (DEFAULT_VMEM_BUDGET, build_tile_map,
                                       choose_meta_layout, meta_table_bytes,
                                       persist_kernel_unsupported,
                                       traverse_whole)
from repro.kernels.traverse.ops import traverse_step

logger = logging.getLogger(__name__)

MODES = ("naive", "rta_like", "staged_noexit", "predicated", "wavefront_host",
         "wavefront", "wavefront_fused", "wavefront_persistent")
#: Modes whose traversal runs fully on-device inside one compiled call.
DEVICE_MODES = ("wavefront", "wavefront_fused", "wavefront_persistent")
#: CSR-frontier modes: multi-scene batches run on the ragged flat frontier.
CSR_MODES = ("wavefront_fused", "wavefront_persistent")
#: Modes whose traversal accepts a static ``max_depth`` cap — the coarser
#: half of the declared degraded mode (DESIGN.md §7).  The per-level arms
#: treat every cap-level node as terminal, so capped verdicts are a
#: conservative superset of full-depth ones.  The persistent megakernel's
#: in-kernel level schedule has no cap; degraded persistent launches
#: shrink the pad bucket only.
DEPTH_CAP_MODES = ("wavefront_host", "wavefront", "wavefront_fused")


def device_loss_count(e: BaseException) -> Optional[int]:
    """Classify an exception as device/mesh loss (DESIGN.md §7): the
    number of shard devices lost, or None if this is not a device-loss
    failure.  Injected :class:`repro.engine.faults.SimulatedDeviceLoss`
    carries a ``device_loss`` attribute and a ``lost`` count; a real
    runtime failure surfaces as an error whose message carries XLA's
    DEVICE_LOST token (count unknown — assume one and let the relaunch
    probe the rest)."""
    if getattr(e, "device_loss", False):
        return max(1, int(getattr(e, "lost", 1)))
    if "DEVICE_LOST" in str(e):
        return 1
    return None


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    mode: str = "wavefront"
    use_spheres: bool = False      # MPAccel bounding/inscribing sphere pre-tests
    max_frontier: int = 1 << 20    # hard cap on live pairs per level
    min_bucket: int = 1024         # smallest frontier allocation
    query_block: int = 128         # naive-mode OBB block size
    frontier_capacity: Optional[int] = None  # device engine: static capacity
    use_pallas_traverse: Optional[bool] = None  # fused step / persistent
    #                                            megakernel; None = auto
    # Persistent-megakernel metadata residency (DESIGN.md §3): budget for
    # the resident node_meta table, and an explicit layout override
    # (None = residency estimator, True = force streamed windows,
    # False = force the resident block).
    vmem_budget: int = DEFAULT_VMEM_BUDGET
    stream_meta: Optional[bool] = None
    # Node-metadata row format for the CSR modes (DESIGN.md §3): None =
    # the layout/format chooser (fp32 when resident fits, else the
    # narrowest eligible compressed format when streaming); "fp32" /
    # "bf16" / "u8" pin it.  Verdicts and work counters are bitwise
    # format-independent; only bytes streamed and VMEM footprint move.
    meta_format: Optional[str] = None
    # Sharded execution (DESIGN.md §6): split the flat pair pool over a
    # 1-D device mesh of this many devices via shard_map.  None =
    # single-device; any int (including 1) routes through the sharded
    # path, whose verdicts and counters are bitwise-identical to
    # single-device (CI-enforced on 8 virtual CPU devices).
    shards: Optional[int] = None

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(
                f"unknown engine mode {self.mode!r}; allowed modes: "
                f"{', '.join(MODES)}")
        if self.shards is not None:
            if self.mode not in DEVICE_MODES:
                raise ValueError(
                    f"shards={self.shards} needs a device-resident mode "
                    f"({', '.join(DEVICE_MODES)}), not {self.mode!r}")
            if self.shards < 1:
                raise ValueError(f"shards must be >= 1, got {self.shards}")
        if self.meta_format is not None:
            if self.meta_format not in META_FORMATS:
                raise ValueError(
                    f"unknown meta_format {self.meta_format!r}; allowed: "
                    f"{', '.join(META_FORMATS)}")
            if self.mode not in CSR_MODES:
                raise ValueError(
                    f"meta_format={self.meta_format!r} needs a CSR mode "
                    f"({', '.join(CSR_MODES)}), not {self.mode!r}: only the "
                    "CSR frontiers decode packed metadata rows")

    @property
    def early_exit(self) -> bool:
        return self.mode in ("predicated", "wavefront_host") + DEVICE_MODES

    @property
    def stage_split(self) -> bool:
        return self.mode in ("wavefront_host",) + DEVICE_MODES

    @property
    def fused(self) -> bool:
        return self.mode == "wavefront_fused"

    @property
    def persistent(self) -> bool:
        return self.mode == "wavefront_persistent"

    @property
    def device_resident(self) -> bool:
        return self.mode in DEVICE_MODES


def _kernel_arm(cfg: EngineConfig) -> bool:
    """Whether Pallas kernels serve this config: pinned by
    ``use_pallas_traverse``, else exactly when the backend is a TPU."""
    if cfg.use_pallas_traverse is not None:
        return cfg.use_pallas_traverse
    return jax.default_backend() == "tpu"


def _bucket(n: int, cfg: EngineConfig) -> int:
    b = cfg.min_bucket
    while b < n:
        b <<= 1
    return min(b, cfg.max_frontier)


def frontier_capacity_bound(level_counts: Sequence[int], num_queries: int,
                            cfg: EngineConfig) -> int:
    """Static worst-case frontier size for a query set against one tree.

    Level l+1 can hold at most 8x the level-l frontier, and never more than
    every query paired with every occupied node of that level.
    """
    if cfg.frontier_capacity is not None:
        return max(cfg.frontier_capacity, num_queries)
    bound = cap = num_queries                # level 0: one root cell
    for n_l in level_counts[1:]:
        bound = min(bound * 8, num_queries * n_l)
        cap = max(cap, bound)
    cap = min(cap, cfg.max_frontier)
    return max(_bucket(cap, cfg), num_queries)


def _initial_capacity(num_queries: int, cfg: EngineConfig) -> int:
    """First-attempt frontier bucket for the escalate-on-overflow policy.

    The level-0 frontier is exactly one pair per query, and with early exit
    most scenes never outgrow that by much — so guess the bucket that holds
    M and let overflow replays buy more only when traversal proves it needs
    it.  Over-guessing costs every level of every query; under-guessing
    costs one replay."""
    if cfg.frontier_capacity is not None:
        return max(cfg.frontier_capacity, num_queries)
    guess = min(max(num_queries, cfg.min_bucket), cfg.max_frontier)
    return max(_bucket(guess, cfg), num_queries)


def _escalate(run, num_queries: int, worst: int, cfg: EngineConfig,
              start: Optional[int] = None):
    """Run ``run(capacity)`` -> (verdict, stats), replaying at 4x capacity
    while the completed call reports frontier overflow.  A pinned
    ``frontier_capacity`` disables escalation (deterministic latency).

    ``start`` seeds the first attempt (the engine remembers the last clean
    capacity per query shape, so repeat queries skip the replay ladder).
    Returns (verdict, stats, clean_capacity, num_replays).
    """
    cap = _initial_capacity(num_queries, cfg)
    if start is not None and cfg.frontier_capacity is None:
        cap = min(max(start, cap), max(worst, num_queries))
    replays = 0
    while True:
        with TraceAnnotation("executor.dispatch", capacity=cap, rung=replays):
            verdict, st = run(cap)
        if cfg.frontier_capacity is not None or cap >= worst:
            return verdict, st, cap, replays
        if _overflowed(st) == 0:
            return verdict, st, cap, replays
        cap = min(max(cap * 4, cfg.min_bucket), worst)
        replays += 1


def _overflowed(st) -> int:
    """Frontier overflow of a finished traversal (a blocking read)."""
    with TraceAnnotation("executor.sync", what="overflow"):
        return int(jax.device_get(jnp.sum(st["overflow"])))


def _tile_frontier_guard(run_arm, memo: dict, memo_key, tag: str):
    """Escalation ``run(cap)`` for a persistent plan: the megakernel
    arm (``run_arm(cap, True)``) while more capacity can still help it,
    else the ref arm (``run_arm(cap, False)``).

    The megakernel holds at most :data:`MAX_TILE_FRONTIER` lanes per
    query tile, however large the global capacity.  Once a kernel run at
    that capacity or above still overflows, a replay cannot grow the
    tile's frontier, so the rest of the ladder runs on the ref arm, whose
    frontier is the whole capacity.  The switch is logged as a warning,
    counted in ``Counters.ref_arm_fallbacks`` and memoized per plan shape
    (repeat plans start on the ref arm).  Returns ``(run, took_ref)``;
    ``took_ref()`` says whether the ref arm served the plan.
    """
    ref_key = ("tile_frontier",) + memo_key
    on_ref = [memo.get(ref_key, False)]

    def run(cap):
        if not on_ref[0]:
            verdict, st = run_arm(cap, True)
            if cap < persist_ops.MAX_TILE_FRONTIER or _overflowed(st) == 0:
                return verdict, st
            logger.warning(
                "persistent plan %s overflows the megakernel's %d-lane "
                "tile frontier at capacity %d; routed to the ref arm",
                tag, persist_ops.MAX_TILE_FRONTIER, cap)
            on_ref[0] = True
            memo[ref_key] = True
        return run_arm(cap, False)

    return run, lambda: on_ref[0]


# ---------------------------------------------------------------------------
# Device-resident traversal (one jit-compiled while_loop, no host syncs)
# ---------------------------------------------------------------------------

def _empty_stats():
    return dict(
        nodes=jnp.int32(0), leaf=jnp.int32(0), axis_exec=jnp.int32(0),
        axis_dec=jnp.int32(0), sphere=jnp.int32(0), overflow=jnp.int32(0),
        per_level=jnp.zeros((MAX_DEPTH + 1,), jnp.int32),
        exit_hist=jnp.zeros((NUM_EXIT_CODES,), jnp.int32))


def _verdict_init(num_queries: int, grouped: bool):
    """Boolean verdicts (one per query) or payload-lane int32 ``best`` cells.

    Grouped verdicts are allocated one cell per query slot regardless of the
    plan's group count (owner ids are compact, ``G <= Q``; the executor
    slices the first G cells after the call) so the group count never
    becomes a compile-time constant — refinement rounds with shifting group
    counts reuse the same traced traversal.
    """
    if not grouped:
        return jnp.zeros((num_queries,), bool)
    return jnp.full((num_queries,), PAYLOAD_INF, jnp.int32)


def _lane_payload(payload, q_idx):
    return (jnp.zeros(q_idx.shape, jnp.int32) if payload is None
            else payload[q_idx])


def _lane_owner(owner, q_idx):
    return q_idx if owner is None else owner[q_idx]


def _traverse(obb_c, obb_h, obb_r, dev: DeviceOctree, capacity: int,
              use_spheres: bool, owner=None, payload=None,
              num_valid=None, max_depth: Optional[int] = None):
    """Full multi-level wavefront traversal for one query set / one scene.

    Pure function of device arrays; composes under jit and vmap.  Returns
    (verdict, stats dict) — (M,) bool collide flags, or with owner /
    payload lanes the (M,) int32 payload-lane ``best`` array (cells past
    the plan's group count unused).

    ``num_valid`` (traced int32, default all M) marks the pool's live
    prefix: slots past it never seed the frontier and add zero work to
    every counter, so a padded pool traverses bitwise like its unpadded
    prefix (the sharded executor's per-shard padding relies on this).

    ``max_depth`` (static) caps traversal at that level: every node of
    the cap level is treated terminal, so an overlap there counts as a
    hit.  Capped verdicts are a conservative SUPERSET of the full-depth
    ones (possible false positives at cap-cell granularity, never a
    missed collision) — the declared degraded mode of DESIGN.md §7.
    """
    M = obb_c.shape[0]
    grouped = owner is not None or payload is not None
    depth = dev.depth if max_depth is None else min(dev.depth, max_depth)
    lane = jnp.arange(capacity, dtype=jnp.int32)
    eight = jnp.arange(8, dtype=jnp.uint32)

    def level_row(arr, level):
        return jax.lax.dynamic_index_in_dim(arr, level, keepdims=False)

    def body(carry):
        level, n_live, q_idx, codes, verdict, st = carry
        valid = lane < n_live
        cell = level_row(dev.cell_sizes, level)
        node_c, node_h = node_centers_from_codes(codes, dev.scene_lo, cell)
        res = sact_mod.sact_frontier(
            obb_c[q_idx], obb_h[q_idx], obb_r[q_idx], node_c, node_h, valid,
            use_spheres=use_spheres)

        # Terminal nodes: leaves, or internal nodes with a full subtree.
        codes_l = level_row(dev.codes, level)
        pos = jnp.clip(jnp.searchsorted(codes_l, codes), 0,
                       codes_l.shape[0] - 1)
        is_term = jnp.where(level == depth, True, level_row(dev.full, level)[pos])
        overlap = res.collide & valid
        term_hit = overlap & is_term
        if grouped:
            pay = _lane_payload(payload, q_idx)
            own = _lane_owner(owner, q_idx)
            verdict = payload_min_update(verdict, own, pay, term_hit)
            undecided = pay < verdict[own]
        else:
            verdict = verdict.at[q_idx].max(term_hit)
            undecided = ~verdict[q_idx]

        # ---- work accounting (device-side; fetched once post-call) -------
        n_valid = jnp.sum(valid.astype(jnp.int32))
        term_valid = (valid & is_term).astype(jnp.int32)
        st = dict(
            nodes=st["nodes"] + n_valid,
            leaf=st["leaf"] + jnp.sum(term_valid),
            axis_exec=st["axis_exec"] + jnp.sum(res.axis_tests),
            axis_dec=st["axis_dec"] + n_valid * NUM_AXES,
            sphere=st["sphere"] + jnp.sum(res.sphere_tests),
            overflow=st["overflow"],
            per_level=st["per_level"].at[level].set(n_valid),
            exit_hist=st["exit_hist"].at[res.exit_code].add(term_valid))

        # ---- expansion + on-device stream compaction ---------------------
        child_codes_l = level_row(dev.codes, jnp.minimum(level + 1, depth))
        cand = (codes[:, None] << jnp.uint32(3)) | eight[None, :]   # (cap, 8)
        cpos = jnp.clip(
            jnp.searchsorted(child_codes_l, cand.reshape(-1)), 0,
            child_codes_l.shape[0] - 1).reshape(cand.shape)
        found = child_codes_l[cpos] == cand
        # Early exit: decided queries retire their whole wavefront share.
        expand = overlap & ~is_term & undecided
        child_mask = (expand[:, None] & found).reshape(-1)          # (cap*8,)
        n_new = jnp.sum(child_mask.astype(jnp.int32))
        cnt, q_next, codes_next = compact_pairs(
            child_mask, jnp.repeat(q_idx, 8), cand.reshape(-1), capacity)
        st["overflow"] = st["overflow"] + jnp.maximum(n_new - capacity, 0)
        return level + 1, cnt, q_next, codes_next, verdict, st

    def cond(carry):
        level, n_live = carry[0], carry[1]
        return (level <= depth) & (n_live > 0)

    q0 = jnp.where(lane < M, lane, 0)
    nv = jnp.asarray(M if num_valid is None else num_valid, jnp.int32)
    carry0 = (jnp.int32(0), jnp.minimum(nv, jnp.int32(capacity)),
              q0, jnp.zeros((capacity,), jnp.uint32),
              _verdict_init(M, grouped), _empty_stats())
    _, _, _, _, verdict, st = jax.lax.while_loop(cond, body, carry0)
    return verdict, st


def _traverse_fused(obb_c, obb_h, obb_r, dev: DeviceOctree, capacity: int,
                    use_spheres: bool, use_pallas_traverse: Optional[bool],
                    owner=None, payload=None, num_valid=None,
                    max_depth: Optional[int] = None):
    """Fused multi-level wavefront traversal (``mode="wavefront_fused"``).

    Same while_loop skeleton and work accounting as :func:`_traverse`, but
    each level is one :func:`repro.kernels.traverse.ops.traverse_step`: the
    frontier carries (query, CSR node index) pairs — codes, terminality and
    child occupancy are O(1) CSR gathers instead of searchsorted probes —
    the staged SACT culls in two phases, and the per-level HBM-resident
    intermediates reduce to frontier-in / frontier-out.  Verdicts and work
    counters are bitwise-identical to :func:`_traverse`.

    ``max_depth`` (static) stops traversal at that level; the step kernel
    only treats TRUE leaves/full subtrees as terminal, so the cap level's
    still-internal overlaps are folded into the verdict here — every
    overlap at the cap counts as a hit, keeping capped verdicts the same
    conservative superset :func:`_traverse` produces (boolean plans only;
    the executor never routes grouped plans through a depth cap).
    """
    M = obb_c.shape[0]
    depth = dev.depth if max_depth is None else min(dev.depth, max_depth)
    capped = depth < dev.depth
    assert not (capped and (owner is not None or payload is not None)), \
        "depth-capped traversal serves boolean plans only"
    lane = jnp.arange(capacity, dtype=jnp.int32)

    def body(carry):
        level, n_live, q_idx, node_idx, verdict, st = carry
        n_next, q_next, idx_next, verdict, info = traverse_step(
            obb_c, obb_h, obb_r, dev, level, n_live, q_idx, node_idx,
            verdict, use_spheres=use_spheres,
            use_pallas=use_pallas_traverse, owner=owner, payload=payload)
        res, valid, is_term = info["res"], info["valid"], info["is_term"]
        if capped:
            cap_hit = (res.collide & valid & ~is_term
                       & (level == jnp.int32(depth)))
            verdict = verdict.at[q_idx].max(cap_hit)

        # ---- work accounting (identical formulas to the unfused arm) -----
        n_valid = jnp.sum(valid.astype(jnp.int32))
        term_valid = (valid & is_term).astype(jnp.int32)
        st = dict(
            nodes=st["nodes"] + n_valid,
            leaf=st["leaf"] + jnp.sum(term_valid),
            axis_exec=st["axis_exec"] + jnp.sum(res.axis_tests),
            axis_dec=st["axis_dec"] + n_valid * NUM_AXES,
            sphere=st["sphere"] + jnp.sum(res.sphere_tests),
            overflow=st["overflow"] + jnp.maximum(info["n_new"] - capacity,
                                                  0),
            per_level=st["per_level"].at[level].set(n_valid),
            exit_hist=st["exit_hist"].at[res.exit_code].add(term_valid))
        return level + 1, n_next, q_next, idx_next, verdict, st

    def cond(carry):
        level, n_live = carry[0], carry[1]
        return (level <= depth) & (n_live > 0)

    q0 = jnp.where(lane < M, lane, 0)
    nv = jnp.asarray(M if num_valid is None else num_valid, jnp.int32)
    carry0 = (jnp.int32(0), jnp.minimum(nv, jnp.int32(capacity)),
              q0, jnp.zeros((capacity,), jnp.int32),
              _verdict_init(M, owner is not None or payload is not None),
              _empty_stats())
    out = jax.lax.while_loop(cond, body, carry0)
    return out[4], out[5]


#: Trace counts per cached-traversal key; Python side effects run only at
#: trace time, so a key whose count stays 1 proved its cache hits.
_TRACE_COUNTS: dict = {}

#: Sentinel for "use the config's value" in per-call overrides.
_UNSET = object()


@functools.lru_cache(maxsize=None)
def _traversal_fn(mode: str, batch: str, capacity: int, use_spheres: bool,
                  use_pallas_traverse, streamed: bool = False,
                  meta_format: str = "fp32",
                  max_depth: Optional[int] = None):
    """One jit-compiled traversal per (mode, batch kind, capacity, statics).

    The LRU gives every (mode, capacity, ...) configuration a *stable
    callable identity*, so jax.jit's shape-keyed cache persists across
    overflow-escalation replays and across repeated ``CollisionEngine``
    constructions on same-shaped scenes — neither retraces.  See
    :func:`traversal_cache_info` for the observability hook tests use.

    ``streamed`` / ``meta_format`` are the persistent megakernel's
    metadata-residency layout and packed row format (the executor's
    chooser picks them per engine, so the choice is part of this cache
    key like every other static — ``meta_format`` also rides the device
    tree's pytree aux, which is what actually drives the traced decode;
    keying it here keeps the cache observability honest when the same
    engine shape flips format).
    """
    key = (mode, batch, capacity, use_spheres, use_pallas_traverse,
           streamed, meta_format, max_depth)

    def base(c, h, r, d, soq=None, owner=None, payload=None, tiles=None):
        _TRACE_COUNTS[key] = _TRACE_COUNTS.get(key, 0) + 1
        with jax.named_scope("collide_traversal"):
            if mode == "wavefront_persistent" or soq is not None or \
                    tiles is not None:
                assert max_depth is None, ("the persistent/ragged arms "
                                           "have no depth cap (DESIGN.md §7)")
                # Whole-traversal megakernel / live-prefix ref; the ragged
                # multi-scene flat frontier (soq or a pre-built tile map)
                # also lands here for every CSR mode.  Only the persistent
                # mode may take the megakernel arm — the fused mode's ragged
                # pool is ref-served so its counters stay the per-level
                # arm's (its own Pallas kernel is the per-level step).
                kernel = (use_pallas_traverse
                          if mode == "wavefront_persistent" else False)
                return traverse_whole(c, h, r, d, capacity,
                                      use_spheres=use_spheres,
                                      use_pallas=kernel, scene_of_query=soq,
                                      owner_of_query=owner, payload=payload,
                                      streamed=streamed, tiles=tiles)
            if mode == "wavefront_fused":
                return _traverse_fused(c, h, r, d, capacity, use_spheres,
                                       use_pallas_traverse, owner=owner,
                                       payload=payload, max_depth=max_depth)
            return _traverse(c, h, r, d, capacity, use_spheres, owner=owner,
                             payload=payload, max_depth=max_depth)

    if batch == "single":
        fn = base
    elif batch == "scenes":      # padded stacked scenes (legacy vmap path)
        def fn(c, h, r, d, soq=None, owner=None, payload=None, tiles=None):
            assert soq is None and owner is None and payload is None \
                and tiles is None, \
                "the padded-scenes vmap path has no scene/owner/payload lanes"
            return jax.vmap(lambda cc, hh, rr, dd: base(cc, hh, rr, dd))(
                c, h, r, d)
    else:
        raise ValueError(f"unknown batch kind {batch!r}; the plan/executor "
                         f"split serves 'single' (flat pool) and 'scenes'")
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _sharded_traversal_fn(mode: str, capacity: int, use_spheres: bool,
                          use_pallas_traverse, streamed: bool,
                          shards: int, max_depth: Optional[int] = None):
    """Sharded sibling of :func:`_traversal_fn` (DESIGN.md §6).

    One shard_map-wrapped jit-compiled traversal per (mode, capacity,
    statics, shard count): the flat pool — padded by the executor so the
    shard count divides it — splits into equal contiguous blocks over the
    collision mesh, the scene tables replicate, and each device traverses
    its block with the SAME per-shard frontier capacity a single-device
    run would use, masking its pad slots via the live-prefix ``num_valid``
    lane.  Work counters psum to the single-device values; ``overflow``
    is a global max so the host escalation loop replays all shards in
    lockstep (see :func:`repro.parallel.sharding.shard_collision_traversal`).
    """
    from repro.parallel.sharding import (make_collision_mesh,
                                         shard_collision_traversal)
    key = (mode, "sharded", capacity, use_spheres, use_pallas_traverse,
           streamed, shards, max_depth)

    def local(nv, c, h, r, d):
        _TRACE_COUNTS[key] = _TRACE_COUNTS.get(key, 0) + 1
        with jax.named_scope("collide_traversal"):
            if mode == "wavefront_persistent":
                assert max_depth is None, \
                    "the persistent arm has no depth cap (DESIGN.md §7)"
                return traverse_whole(c, h, r, d, capacity,
                                      use_spheres=use_spheres,
                                      use_pallas=use_pallas_traverse,
                                      streamed=streamed, num_valid=nv)
            if mode == "wavefront_fused":
                return _traverse_fused(c, h, r, d, capacity, use_spheres,
                                       use_pallas_traverse, num_valid=nv,
                                       max_depth=max_depth)
            return _traverse(c, h, r, d, capacity, use_spheres, num_valid=nv,
                             max_depth=max_depth)

    mesh = make_collision_mesh(shards)
    sm = jax.jit(shard_collision_traversal(local, mesh))

    def call(counts, c, h, r, d):
        # The wrapper's stats come back with a leading shard axis of
        # identical (already psum/pmax-reduced) rows; read row 0 so the
        # escalation loop and counter assembly see single-device shapes.
        verdict, st = sm(counts, c, h, r, d)
        return verdict, {k: v[0] for k, v in st.items()}

    return call


def traversal_cache_info() -> dict:
    """Cache observability: lru entries + per-key trace counts."""
    info = _traversal_fn.cache_info()
    sharded = _sharded_traversal_fn.cache_info()
    return dict(hits=info.hits, misses=info.misses,
                entries=info.currsize, sharded_entries=sharded.currsize,
                traces=dict(_TRACE_COUNTS))


def _stats_to_counters(st, mode: str, replays: int = 0,
                       extra_lanes: int = 0,
                       meta_format: str = "fp32") -> Counters:
    with TraceAnnotation("executor.sync", what="counters"):
        st = jax.device_get(st)
    c = Counters()

    def tot(x):
        return int(np.sum(np.asarray(st[x], np.int64)))

    c.nodes_traversed = tot("nodes")
    c.leaf_tests = tot("leaf")
    c.axis_tests_executed = tot("axis_exec")
    c.axis_tests_decoded = tot("axis_dec")
    c.sphere_tests = tot("sphere")
    c.frontier_overflow = tot("overflow")
    c.escalations = replays
    per = np.asarray(st["per_level"], np.int64)
    if per.ndim > 1:                       # batched: sum lanes per level
        per = per.reshape(-1, per.shape[-1]).sum(axis=0)
    c.nodes_per_level = [int(n) for n in per if n > 0]
    hist = np.asarray(st["exit_hist"], np.int64)
    c.exit_histogram += hist.reshape(-1, hist.shape[-1]).sum(axis=0)
    if "meta_rows" in st:
        c.meta_rows_streamed = tot("meta_rows")
    if "meta_gathers" in st:
        c.meta_gathers = tot("meta_gathers")
    # Streamed rows are priced at the packed row format's width (the row
    # COUNT is format-independent — see counters.py).
    row_bytes = {"fp32": BYTES_META_STREAM, "bf16": BYTES_META_STREAM_BF16,
                 "u8": BYTES_META_STREAM_U8}[meta_format]
    c.meta_bytes_streamed = c.meta_rows_streamed * row_bytes
    # Bytes models (see counters.py): per-level arms move the frontier
    # through HBM every level; the persistent megakernel only moves each
    # query's seed in / verdict out, plus the streamed layout's metadata
    # window rows.  Grouped plans pay one extra int32 lane per frontier
    # pair (per seed, for the persistent arm) for each lane they carry —
    # owner and/or payload.
    extra = BYTES_PAYLOAD_LANE * extra_lanes
    if mode == "wavefront_persistent":
        seeds = int(per[0]) if per.size else 0
        c.bytes_moved = (seeds * (BYTES_PERSIST_QUERY + extra)
                         + c.meta_bytes_streamed)
    elif mode == "wavefront_fused":
        c.bytes_moved = c.nodes_traversed * (BYTES_FUSED_STEP + extra)
    else:
        c.bytes_moved = c.nodes_traversed * (BYTES_UNFUSED_TEST + extra)
    return c


@functools.partial(jax.jit, static_argnames=("use_spheres", "stage_split"))
def _test_pairs(obb_c, obb_h, obb_r, node_c, node_h, valid,
                use_spheres: bool, stage_split: bool) -> SactResult:
    """Staged SACT on a host-managed frontier of pairs.

    With ``stage_split`` the edge axes are evaluated behind a
    ``lax.select``-style mask (their cost is counted separately by the work
    model); the wall-clock stage split happens at the frontier level via
    bucket resizing, which is where static-shape hardware can actually save.
    """
    del stage_split
    return sact_mod.sact_frontier(obb_c, obb_h, obb_r, node_c, node_h, valid,
                                  use_spheres=use_spheres)


@functools.partial(jax.jit, static_argnames=("n_out",))
def _compact(mask: jax.Array, n_out: int, *arrays):
    """Pack entries where mask is True to the front of fresh (n_out,) arrays."""
    idx = jnp.nonzero(mask, size=n_out, fill_value=mask.shape[0])[0]
    in_range = idx < mask.shape[0]
    idx_c = jnp.minimum(idx, mask.shape[0] - 1)
    out = tuple(jnp.where(in_range.reshape((-1,) + (1,) * (a.ndim - 1)),
                          a[idx_c], 0) for a in arrays)
    return (in_range,) + out


#: Device scene-table memo for repeat multi-scene batches: building the
#: concatenated/stacked level tables is a host-side numpy pass over every
#: level of every scene plus a device transfer — far more than a warm
#: traversal costs.  Keyed by the octree objects' identities; weakrefs
#: guard against id reuse after GC (a dead ref can never alias a live key).
_TABLE_CACHE: dict = {}
_TABLE_CACHE_MAX = 8


def _scene_tables(octrees: List[Octree], padded: bool, fmt: str = "fp32"):
    key = (padded, fmt, tuple(id(t) for t in octrees))
    hit = _TABLE_CACHE.get(key)
    if hit is not None:
        refs, tables = hit
        if all(r() is t for r, t in zip(refs, octrees)):
            return tables
    tables = (stack_device_octrees(octrees) if padded
              else concat_device_octrees(octrees, meta_format=fmt))
    while len(_TABLE_CACHE) >= _TABLE_CACHE_MAX:
        _TABLE_CACHE.pop(next(iter(_TABLE_CACHE)))
    _TABLE_CACHE[key] = ([weakref.ref(t) for t in octrees], tables)
    return tables


def _stage(plan: QueryPlan) -> QueryPlan:
    """The plan with its host arrays (OBB fields and any lanes) placed on
    the default device by one ``jax.device_put``, under an
    ``executor.stage`` span: the transfer jit's argument handling would
    otherwise make inside the dispatch.  Device arrays pass through."""
    lanes = ("obb_c", "obb_h", "obb_r", "scene_of_query", "owner_of_query",
             "payload")
    host = {f: getattr(plan, f) for f in lanes}
    host = {f: a for f, a in host.items()
            if a is not None and not isinstance(a, jax.Array)}
    with TraceAnnotation("executor.stage", queries=plan.num_queries):
        return dataclasses.replace(plan, **jax.device_put(host))


def _fetch_verdict(verdict) -> np.ndarray:
    with TraceAnnotation("executor.sync", what="verdict"):
        return np.asarray(jax.device_get(verdict))


class CollisionEngine:
    """Octree collision queries for fixed scene(s), in a selectable mode.

    The engine is the executor of :class:`repro.engine.plan.QueryPlan`:
    ``execute`` serves any lowered plan, and ``query`` /
    ``query_batched`` are thin front-ends that build the obvious plan.
    Construct with one :class:`Octree` for single-scene service or a list
    for multi-scene plans (``plan_scenes``).
    """

    def __init__(self, octree: Union[Octree, List[Octree]],
                 config: EngineConfig = EngineConfig()):
        self.cfg = config
        # Last clean frontier capacity per (query shape, scene signature):
        # repeat queries start there instead of re-climbing the escalation
        # ladder.  The scene node counts are part of every key so a
        # rebind to a grown scene can never reuse a stale clean capacity
        # (which could skip the ladder and silently overflow-spill).
        self._cap_memo: dict = {}
        # Device-loss seam (DESIGN.md §7): an optional callable invoked
        # with the shard count at the top of every sharded launch attempt
        # — the chaos harness installs one that raises SimulatedDeviceLoss
        # so the re-shard/relaunch recovery below it is exercised, not
        # just the batcher's typed-error translation.
        self.device_fault_injector = None
        # Surviving shard count after device loss (None = all of
        # cfg.shards healthy).  Sticky across calls — lost devices do not
        # come back on their own; ``set_shards`` re-probes the full set.
        self._healthy_shards: Optional[int] = None
        self.rebind_octrees(octree)

    def rebind_octrees(self, octree: Union[Octree, List[Octree]]) -> None:
        """(Re)bind the engine to new scene(s), keeping config and caches.

        Growing a scene between calls is a supported pattern (e.g. a
        mapping robot accreting points): derived device state is rebuilt
        lazily, and the clean-capacity memo — which survives the rebind —
        is keyed on the scenes' node counts, so queries against the grown
        scene re-enter the escalation ladder instead of inheriting the old
        scene's (possibly too small, silently spilling) clean capacity.
        """
        self.octrees = (list(octree) if isinstance(octree, (list, tuple))
                        else [octree])
        self.octree = self.octrees[0]
        self._scene_lo = jnp.asarray(self.octree.scene_lo)
        self._level_codes = [jnp.asarray(l.codes) for l in self.octree.levels]
        self._level_full = [jnp.asarray(l.full) for l in self.octree.levels]
        self._dev: dict = {}               # packed device tables by format
        # The layout/format choice depends on the bound scene's size
        # class, so a rebind must re-run the chooser: a scene grown past
        # a residency or format-eligibility boundary would otherwise keep
        # a stale (layout, format) decision — and with it a stale cache
        # key — from the smaller scene.
        self._meta_choice = None
        # Per-scene total node counts: the memo-key scene signature.
        self._scene_sig = tuple(
            sum(len(l.codes) for l in t.levels) for t in self.octrees)
        # Every memo key ends with the scene signature; entries for
        # superseded scenes can never be read again, so drop them — a
        # long accreting-scene loop keeps the memo bounded by the query
        # shapes of the CURRENT scene.
        self._cap_memo = {k: v for k, v in self._cap_memo.items()
                          if k[-1] == self._scene_sig}

    # ------------------------------------------------------------------
    # Elastic sharding surface (DESIGN.md §6/§7): the batcher reads
    # active_shards / scene_nodes and rescales via set_shards.
    # ------------------------------------------------------------------
    @property
    def scene_nodes(self) -> int:
        """Total node count of the bound scene(s) — the per-query factor
        of the service's predicted-work admission estimate."""
        return sum(self._scene_sig)

    @property
    def active_shards(self) -> Optional[int]:
        """Shards the next sharded launch will use: ``cfg.shards`` minus
        devices lost to (possibly injected) device-loss recoveries; None
        for an unsharded engine."""
        if self.cfg.shards is None:
            return None
        return (self._healthy_shards if self._healthy_shards is not None
                else self.cfg.shards)

    @property
    def supports_depth_cap(self) -> bool:
        """Whether ``execute(plan, max_depth=...)`` can cap this engine's
        traversal depth (the coarser half of the degraded mode)."""
        return self.cfg.mode in DEPTH_CAP_MODES

    def set_shards(self, shards: int) -> None:
        """Elastic width: rebind the engine to an ``shards``-device
        collision mesh (the batcher's autoscaler calls this between
        launches).  Resets the device-loss bookkeeping — a rescale
        re-probes the full device set, which is how a recovered device
        rejoins the mesh."""
        if self.cfg.shards is None:
            raise ValueError(
                "set_shards needs an engine constructed with cfg.shards; "
                "unsharded engines have no collision mesh to resize")
        n_dev = len(jax.devices())
        if not 1 <= shards <= n_dev:
            raise ValueError(
                f"shards must be in [1, {n_dev}] (visible devices), "
                f"got {shards}")
        self.cfg = dataclasses.replace(self.cfg, shards=shards)
        self._healthy_shards = None

    def _device_tree(self, fmt: str) -> DeviceOctree:
        """Padded level arrays packed in ``fmt``, cached per format."""
        if fmt not in self._dev:
            self._dev[fmt] = device_octree(self.octree, meta_format=fmt)
        return self._dev[fmt]

    def _replicated_tree(self, shards: int) -> DeviceOctree:
        """The fp32 scene tables placed once on every device of the
        ``shards``-device collision mesh, so a sharded launch does not
        copy them out from one device each time."""
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.parallel.sharding import make_collision_mesh
        key = ("replicated", shards)
        if key not in self._dev:
            self._dev[key] = jax.device_put(
                self._device_tree("fp32"),
                NamedSharding(make_collision_mesh(shards), PartitionSpec()))
        return self._dev[key]

    @property
    def device_tree(self) -> DeviceOctree:
        """Packed level arrays for the device-resident engine (lazy); the
        CSR modes get this engine's chosen row format, the Morton-code
        frontier (``mode="wavefront"``) always fp32 (it never reads the
        packed rows, but shares the table builder)."""
        fmt = self.meta_format if self.cfg.mode in CSR_MODES else "fp32"
        return self._device_tree(fmt)

    def _choose_meta(self):
        """Run (and memoize) the layout x format chooser for this engine's
        scene(s).  Multi-scene engines size the CONCATENATED flat table
        (per-level totals across scenes) — the table the CSR modes
        actually hold — so ragged batches stream and compress on the same
        budget rules as single scenes."""
        if self._meta_choice is None:
            n_levels = max(len(t.levels) for t in self.octrees)
            n_max = max(
                sum(len(t.levels[l].codes) if l < len(t.levels) else 0
                    for t in self.octrees)
                for l in range(n_levels))
            layout = (None if self.cfg.stream_meta is None else
                      ("streamed" if self.cfg.stream_meta else "resident"))
            self._meta_choice = choose_meta_layout(
                self.octree.depth, n_max, self.cfg.vmem_budget,
                fmt=self.cfg.meta_format, layout=layout)
        return self._meta_choice

    @property
    def meta_layout(self) -> str:
        """Persistent-megakernel metadata residency for this engine's
        scene: ``"resident"`` or ``"streamed"`` (DESIGN.md §3).  Driven by
        the layout/format chooser against ``cfg.vmem_budget`` unless
        ``cfg.stream_meta`` pins it; feeds the traversal cache key."""
        return self._choose_meta().layout

    @property
    def meta_format(self) -> str:
        """Packed node-metadata row format for this engine's scene
        ("fp32" | "bf16" | "u8", DESIGN.md §3).  ``cfg.meta_format`` pins
        it; otherwise the chooser's pick for the persistent megakernel,
        and fp32 for every other mode (the fused arm decodes any format
        but only compresses when asked — its table is never the VMEM
        bound)."""
        if self.cfg.meta_format is not None:
            return self.cfg.meta_format
        if self.cfg.persistent:
            return self._choose_meta().fmt
        return "fp32"

    def _capacity(self, num_queries: int) -> int:
        counts = [len(l.codes) for l in self.octree.levels]
        return frontier_capacity_bound(counts, num_queries, self.cfg)

    # ------------------------------------------------------------------
    # Front-ends: build the obvious plan, execute it.
    # ------------------------------------------------------------------
    def query(self, obbs: OBBs) -> Tuple[np.ndarray, Counters]:
        return self.execute(plan_queries(obbs))

    def query_batched(self, obbs: OBBs) -> Tuple[np.ndarray, Counters]:
        """Batched front-end: OBB fields carry a leading batch axis.

        ``obbs.center`` is (B, M, 3) (likewise half/rot); the batch lowers
        to ONE flat pool of B * M query slots traversed in a single
        compiled call — for host modes too, which is what lets benchmarks
        report the device speedup on identical work.  Returns ((B, M)
        verdicts, aggregate counters).
        """
        return self.execute(plan_batch(obbs))

    # ------------------------------------------------------------------
    # The executor.
    # ------------------------------------------------------------------
    def execute(self, plan: QueryPlan,
                max_depth: Optional[int] = None
                ) -> Tuple[np.ndarray, Counters]:
        """Run one lowered plan; returns (un-flattened verdicts, counters).

        Boolean plans yield bool verdicts in the plan's native shape;
        payload-lane plans yield the int32 per-group ``best`` payloads
        (``PAYLOAD_INF`` = group never hit).

        ``max_depth`` caps traversal depth for degraded-mode service
        (``DEPTH_CAP_MODES`` only; single-scene boolean plans): the cap
        level is treated terminal, so verdicts are a conservative
        superset of the full-depth run — coarser, never missing a
        collision.
        """
        with TraceAnnotation("engine.execute", queries=plan.num_queries,
                             kind=plan.kind):
            t0 = time.perf_counter()
            if plan.num_scenes != len(self.octrees):
                raise ValueError(
                    f"plan carries {plan.num_scenes} scene(s) but the engine "
                    f"holds {len(self.octrees)}")
            assert plan.num_scenes == 1 or self.cfg.device_resident, \
                "multi-scene batching needs a device mode"
            if plan.grouped and not self.cfg.device_resident:
                raise ValueError(
                    "owner/payload plans need a device-resident mode; lower "
                    "to a boolean plan and reduce on the host instead")
            if max_depth is not None:
                if not self.supports_depth_cap:
                    raise ValueError(
                        f"max_depth needs a depth-cappable mode "
                        f"({', '.join(DEPTH_CAP_MODES)}), not "
                        f"{self.cfg.mode!r}")
                if plan.grouped or plan.num_scenes > 1:
                    raise ValueError(
                        "max_depth serves single-scene boolean plans (the "
                        "degraded service path); grouped/multi-scene plans "
                        "run at full depth")
                if max_depth < 1:
                    raise ValueError(
                        f"max_depth must be >= 1, got {max_depth}")
            if self.cfg.shards is not None:
                value, counters = self._exec_sharded(plan, max_depth)
            elif self.cfg.mode == "naive":
                value, counters = self._exec_naive(plan)
            elif self.cfg.device_resident:
                value, counters = self._exec_device(plan, max_depth)
            else:
                value, counters = self._exec_host(plan, max_depth)
            counters.wall_time_s = time.perf_counter() - t0
            counters.num_queries = plan.num_queries
            return plan.unflatten(value), counters

    # ------------------------------------------------------------------
    def _run(self, capacity: int, batch: str = "single",
             streamed: bool = False, meta_format: str = "fp32",
             use_pallas_traverse=_UNSET, max_depth: Optional[int] = None):
        """Cached jit-compiled traversal for this engine's config.

        ``use_pallas_traverse`` overrides the config's setting (the
        persistent executor resolves arm routing per plan — capability
        fallbacks pin the ref arm for that plan only)."""
        upt = (self.cfg.use_pallas_traverse
               if use_pallas_traverse is _UNSET else use_pallas_traverse)
        return _traversal_fn(self.cfg.mode, batch, capacity,
                             self.cfg.use_spheres, upt, streamed,
                             meta_format, max_depth)

    def _guarded(self, run_arm, upt, memo_key, plan: QueryPlan):
        """``(run, took_ref)`` for one plan's escalation ladder: the
        persistent mode's kernel arm goes through
        :func:`_tile_frontier_guard`; every other arm runs as ``upt``."""
        if not (self.cfg.persistent and upt):
            return (lambda cap: run_arm(cap, upt)), (lambda: False)
        return _tile_frontier_guard(run_arm, self._cap_memo, memo_key,
                                    plan.shape_tag)

    def _exec_device(self, plan: QueryPlan,
                     max_depth: Optional[int] = None):
        cfg = self.cfg
        Q = plan.num_queries
        owner = plan.owner_of_query
        # Routing and tiling below read ``plan``'s own (host) lanes; the
        # traversal reads the staged copies.
        staged = _stage(plan)
        fmt = self.meta_format if cfg.mode in CSR_MODES else "fp32"
        # Metadata residency is picked here, per (mode, statics) cache
        # key, so paper-scale scenes run the persistent megakernel with
        # streamed windows instead of needing a different mode — for
        # EVERY plan shape: ragged multi-scene batches and cross-slot
        # owner (swept-edge) plans are owner-group tiled onto the same
        # kernel (per-scene sub-level windows key each tile's schedule
        # to its own scene), so they stream and compress like single
        # scenes.
        streamed = cfg.persistent and self.meta_layout == "streamed"
        # Kernel-arm routing (persistent mode): the only ref-arm routes
        # left are named capability gaps — counted in
        # ``Counters.ref_arm_fallbacks`` and logged with the plan shape,
        # never silent.
        kernel_arm = _kernel_arm(cfg)
        fallback_reason = None
        if cfg.persistent:
            fallback_reason = persist_kernel_unsupported(
                owner, plan.scene_of_query)
            if fallback_reason is not None:
                if kernel_arm:
                    logger.warning(
                        "persistent plan %s routed to the ref arm: %s",
                        plan.shape_tag, fallback_reason)
                kernel_arm = False
        upt = kernel_arm if cfg.persistent else cfg.use_pallas_traverse
        # Plans whose verdict groups or scenes cross query-tile
        # boundaries run as an owner-group tiled pool (pre-built here,
        # eagerly — the tile map needs concrete ids — and passed through
        # jit as arrays); capability fallbacks keep the untiled legacy
        # ref routing.
        tiled = (cfg.persistent and fallback_reason is None
                 and (plan.num_scenes > 1 or owner is not None))
        tiles = None
        if tiled:
            tm = build_tile_map(
                Q, 128,
                None if plan.scene_of_query is None
                else np.asarray(plan.scene_of_query),
                None if owner is None else np.asarray(owner))
            perm = np.maximum(tm.perm, 0)
            run_args = (staged.obb_c[perm], staged.obb_h[perm],
                        staged.obb_r[perm])
            owner_t = (None if owner is None
                       else staged.owner_of_query[perm])
            payload_t = (None if plan.payload is None
                         else staged.payload[perm])
            tiles = jax.tree.map(jnp.asarray, tm.tiles)
        if plan.num_scenes > 1 and cfg.mode in CSR_MODES:
            # Ragged flat frontier: one pool of (scene, query, CSR node)
            # triples over the concatenated multi-scene table.
            multi = _scene_tables(self.octrees, padded=False, fmt=fmt)
            per_scene = Q // plan.num_scenes
            worst = min(
                sum(frontier_capacity_bound([len(l.codes) for l in t.levels],
                                            per_scene, cfg)
                    for t in self.octrees),
                max(cfg.max_frontier, Q))
            memo_key = ("csr_scenes", Q, plan.grouped, self._scene_sig)
            if tiled:
                run_arm = lambda cap, arm: self._run(
                    cap, streamed=streamed, meta_format=fmt,
                    use_pallas_traverse=arm)(
                        *run_args, multi, None, owner_t, payload_t, tiles)
            else:
                run_arm = lambda cap, arm: self._run(
                    cap, streamed=streamed, meta_format=fmt,
                    use_pallas_traverse=arm)(
                        staged.obb_c, staged.obb_h, staged.obb_r, multi,
                        staged.scene_of_query, staged.owner_of_query,
                        staged.payload)
            run, took_ref = self._guarded(run_arm, upt, memo_key, plan)
            verdict, st, cap, replays = _escalate(
                run, Q, worst, cfg, start=self._cap_memo.get(memo_key))
        elif plan.num_scenes > 1:
            # mode="wavefront" keeps the legacy padded-vmap path (its
            # frontier carries Morton codes, not CSR indices) for A/B.
            assert not plan.grouped, \
                "owner/payload plans need a CSR mode for multi-scene batches"
            dev = _scene_tables(self.octrees, padded=True)
            S, M = plan.out_shape
            worst = max(frontier_capacity_bound(
                [len(l.codes) for l in t.levels], M, cfg)
                for t in self.octrees)
            memo_key = ("pad_scenes", S, M, self._scene_sig)
            took_ref = lambda: False
            verdict, st, cap, replays = _escalate(
                lambda cap: self._run(cap, "scenes")(
                    staged.obb_c.reshape(S, M, 3),
                    staged.obb_h.reshape(S, M, 3),
                    staged.obb_r.reshape(S, M, 3, 3), dev),
                M, worst, cfg, start=self._cap_memo.get(memo_key))
        else:
            memo_key = ("single", Q, plan.grouped, max_depth,
                        self._scene_sig)
            if tiled:
                run_arm = lambda cap, arm: self._run(
                    cap, streamed=streamed, meta_format=fmt,
                    use_pallas_traverse=arm)(
                        *run_args, self.device_tree, None, owner_t,
                        payload_t, tiles)
            else:
                run_arm = lambda cap, arm: self._run(
                    cap, streamed=streamed, meta_format=fmt,
                    use_pallas_traverse=arm, max_depth=max_depth)(
                        staged.obb_c, staged.obb_h, staged.obb_r,
                        self.device_tree, None, staged.owner_of_query,
                        staged.payload)
            run, took_ref = self._guarded(run_arm, upt, memo_key, plan)
            verdict, st, cap, replays = _escalate(
                run, Q, self._capacity(Q), cfg,
                start=self._cap_memo.get(memo_key))
        self._cap_memo[memo_key] = cap
        lanes = ((plan.owner_of_query is not None)
                 + (plan.payload is not None))
        counters = _stats_to_counters(st, cfg.mode, replays,
                                      extra_lanes=lanes, meta_format=fmt)
        if cfg.persistent and (fallback_reason is not None or took_ref()):
            counters.ref_arm_fallbacks = 1
        verdict = _fetch_verdict(verdict)
        if plan.grouped:
            # Grouped verdicts are computed in a Q-sized buffer (owner ids
            # are compact); only the first G cells are meaningful.
            verdict = verdict[:plan.groups]
        return verdict, counters

    # ------------------------------------------------------------------
    def _exec_sharded(self, plan: QueryPlan,
                      max_depth: Optional[int] = None):
        """Sharded execute path (``cfg.shards``, DESIGN.md §6).

        The flat pool pads up to a multiple of the shard count (pad slots
        ride in the LAST shard's tail), splits into equal contiguous
        blocks over the collision mesh, and every device traverses its
        block at the same frontier capacity the single-device run would
        use — its true live count travels as a per-shard ``num_valid``
        lane, so pads add zero work.  Verdicts and counters come back
        bitwise-identical to single-device; escalation replays are
        coordinated by the global max over per-shard overflow flags.

        **Device-loss recovery (DESIGN.md §7):** a launch attempt that
        fails with a device-loss-classified error (see
        :func:`device_loss_count`) does not fail the plan — the pool
        re-pads and re-shards over the surviving device set and the
        launch replays there.  Because verdicts and counters are
        bitwise-identical across ANY shard count (the invariant above,
        CI-enforced; ``meta_gathers`` excepted, which counts the
        megakernel's per-tile gathers), the recovered run answers exactly
        like the healthy mesh; only ``Counters.reshards`` / ``shards_lost`` (and the pad
        count) betray that anything happened.  The reduced width is
        sticky on the engine (``active_shards``) until ``set_shards``
        re-probes the full device set; a loss with no survivors
        propagates, which the batcher translates into the typed
        ``DeviceLost`` service error.

        v1 serves single-scene boolean plans; ragged multi-scene pools
        and owner/payload lanes stay single-device (their frontiers are
        not partitioned by query slot).  The streamed metadata layout is
        per-device-tile, so sharded runs pin the resident fp32 layout to
        keep ``meta_rows`` partition-invariant.
        """
        cfg = self.cfg
        Q = plan.num_queries
        if plan.num_scenes != 1:
            raise ValueError(
                "sharded execution serves single-scene plans; multi-scene "
                "pools are single-device for now (DESIGN.md §6)")
        if plan.grouped:
            raise ValueError(
                "sharded execution serves boolean plans; owner/payload "
                "verdict groups span shards and stay single-device")
        if cfg.persistent and _kernel_arm(cfg):
            n_max = max(len(l.codes) for l in self.octree.levels)
            need = meta_table_bytes(self.octree.depth, n_max, "fp32")
            if need > cfg.vmem_budget:
                raise ValueError(
                    f"sharded wavefront_persistent pins the resident fp32 "
                    f"metadata table, which needs {need / 2**20:.1f} MiB of "
                    f"VMEM for this scene (budget "
                    f"{cfg.vmem_budget / 2**20:.1f} MiB); serve it sharded "
                    "with mode='wavefront_fused', or single-device where "
                    "the table streams")
        staged = _stage(plan)
        shards = self.active_shards
        reshards = 0
        lost_total = 0
        while True:
            try:
                if self.device_fault_injector is not None:
                    self.device_fault_injector(shards)
                q_shard = -(-Q // shards)
                pad = q_shard * shards - Q
                obb_c = jnp.pad(staged.obb_c, ((0, pad), (0, 0)))
                obb_h = jnp.pad(staged.obb_h, ((0, pad), (0, 0)))
                obb_r = jnp.pad(staged.obb_r, ((0, pad), (0, 0), (0, 0)))
                counts = jnp.clip(
                    Q - jnp.arange(shards, dtype=jnp.int32) * q_shard,
                    0, q_shard)
                memo_key = ("sharded", shards, Q, max_depth,
                            self._scene_sig)
                run, took_ref = self._guarded(
                    lambda cap, arm: _sharded_traversal_fn(
                        cfg.mode, cap, cfg.use_spheres, arm, False, shards,
                        max_depth)(
                            # Sharded runs pin the resident fp32 table
                            # (see the docstring): per-device window
                            # traffic would break the partition-
                            # invariance of ``meta_rows``.
                            counts, obb_c, obb_h, obb_r,
                            self._replicated_tree(shards)),
                    _kernel_arm(cfg) if cfg.persistent
                    else cfg.use_pallas_traverse, memo_key, plan)
                verdict, st, cap, replays = _escalate(
                    run, Q, self._capacity(Q), cfg,
                    start=self._cap_memo.get(memo_key))
                break
            except Exception as e:
                lost = device_loss_count(e)
                if lost is None:
                    raise
                lost = min(lost, shards)
                surviving = shards - lost
                lost_total += lost
                self._healthy_shards = max(surviving, 1)
                if surviving < 1:
                    logger.error(
                        "collision mesh lost its last %d device(s); "
                        "no survivors to re-shard onto: %s", lost, e)
                    raise
                reshards += 1
                logger.warning(
                    "device loss mid-launch (%d of %d shard devices); "
                    "re-sharding the %d-query pool over the %d survivors",
                    lost, shards, Q, surviving)
                shards = surviving
        self._cap_memo[memo_key] = cap
        counters = _stats_to_counters(st, cfg.mode, replays)
        counters.ref_arm_fallbacks = int(took_ref())
        counters.pad_queries = pad
        counters.reshards = reshards
        counters.shards_lost = lost_total
        verdict = _fetch_verdict(verdict)[:Q]
        return verdict, counters

    # ------------------------------------------------------------------
    def _exec_naive(self, plan: QueryPlan):
        """CUDA-baseline arm: dense all-pairs vs all leaf AABBs, all axes."""
        obbs = plan.obbs
        leaves = self.octree.leaf_aabbs()
        c = Counters()
        M = obbs.n
        res = sact_mod.sact_pairwise_blocked(
            obbs, leaves, block=self.cfg.query_block, use_spheres=False)
        collide = np.asarray(jax.device_get(jnp.any(res.collide, axis=-1)))
        n_tests = M * leaves.n
        c.nodes_traversed = n_tests
        c.leaf_tests = n_tests
        c.axis_tests_executed = n_tests * NUM_AXES
        c.axis_tests_decoded = n_tests * NUM_AXES
        c.bytes_moved = n_tests * BYTES_UNFUSED_TEST
        codes = np.asarray(jax.device_get(res.exit_code)).reshape(-1)
        c.merge_exit_codes(codes, np.ones_like(codes, bool))
        return collide, c

    # ------------------------------------------------------------------
    def _exec_host(self, plan: QueryPlan, max_depth: Optional[int] = None):
        """Legacy host-in-the-loop traversal (``wavefront_host`` and the
        predication/no-exit ablation arms): the frontier is re-bucketed on
        the host between levels, which blocks jit across levels.

        ``max_depth`` caps the level loop, treating the cap level as
        terminal — same conservative-superset contract as the device
        arms."""
        obbs = plan.obbs
        cfg = self.cfg
        oct_ = self.octree
        depth_eff = (oct_.depth if max_depth is None
                     else min(oct_.depth, max_depth))
        M = obbs.n
        c = Counters()
        decided = np.zeros(M, bool)           # queries confirmed colliding
        collide = np.zeros(M, bool)

        if len(oct_.levels[0].codes) == 0:
            return collide, c

        # Frontier at level 0: every query x the root cell.
        q_idx = jnp.arange(M, dtype=jnp.int32)
        codes = jnp.zeros((M,), jnp.uint32)
        n_live = M
        bucket = _bucket(M, cfg)
        q_idx = jnp.pad(q_idx, (0, bucket - M))
        codes = jnp.pad(codes, (0, bucket - M))
        valid = jnp.arange(bucket) < n_live

        for level in range(0, depth_eff + 1):
            if n_live == 0:
                break
            cell = oct_.cell_size(level)
            node_c, node_h = node_centers_from_codes(codes, self._scene_lo,
                                                     cell)
            res = _test_pairs(obbs.center[q_idx], obbs.half[q_idx],
                              obbs.rot[q_idx], node_c, node_h, valid,
                              use_spheres=cfg.use_spheres,
                              stage_split=cfg.stage_split)
            # Terminal nodes: leaves, full internal subtrees, or (when a
            # degraded max_depth caps the loop) everything at the cap.
            if level == depth_eff:
                is_term = jnp.ones_like(valid)
            else:
                pos = jnp.searchsorted(self._level_codes[level], codes)
                pos = jnp.clip(pos, 0, self._level_codes[level].shape[0] - 1)
                is_term = self._level_full[level][pos]
            overlap = res.collide & valid
            term_hit = overlap & is_term

            # ---- work accounting -------------------------------------
            valid_np = np.asarray(jax.device_get(valid))
            n_valid = int(valid_np.sum())
            c.nodes_traversed += n_valid
            c.nodes_per_level.append(n_valid)
            n_term = int(jax.device_get(jnp.sum(valid & is_term)))
            c.leaf_tests += n_term
            exec_tests = int(jax.device_get(
                jnp.sum(jnp.where(valid, res.axis_tests, 0))))
            c.axis_tests_executed += exec_tests
            c.axis_tests_decoded += n_valid * NUM_AXES
            c.sphere_tests += int(jax.device_get(
                jnp.sum(jnp.where(valid, res.sphere_tests, 0))))
            per_test_bytes = (BYTES_FUSED_TEST if cfg.fused
                              else BYTES_UNFUSED_TEST)
            c.bytes_moved += n_valid * per_test_bytes
            if cfg.mode == "rta_like":
                n_hits = int(jax.device_get(jnp.sum(overlap)))
                c.shader_invocations += n_hits
                c.bytes_moved += n_hits * BYTES_SHADER_HANDOFF
            codes_np = np.asarray(jax.device_get(res.exit_code))
            c.merge_exit_codes(codes_np, np.asarray(jax.device_get(
                valid & is_term)))

            # ---- collision confirmation ------------------------------
            hit_q = np.asarray(jax.device_get(
                jnp.zeros(M, bool).at[q_idx].max(term_hit)))
            collide |= hit_q
            if cfg.early_exit:
                decided |= hit_q

            if level == depth_eff:
                break

            # ---- expansion -------------------------------------------
            expand = overlap & ~is_term
            if cfg.early_exit:
                expand = expand & ~jnp.asarray(decided)[q_idx]
            child_codes, child_idx = lookup_children(
                self._level_codes[level + 1], codes)
            child_mask = expand[:, None] & (child_idx >= 0)         # (K, 8)
            flat_mask = child_mask.reshape(-1)
            flat_codes = child_codes.reshape(-1)
            flat_q = jnp.repeat(q_idx, 8)
            n_live = int(jax.device_get(jnp.sum(flat_mask)))
            if n_live == 0:
                break
            if n_live > cfg.max_frontier:
                c.frontier_overflow += n_live - cfg.max_frontier
                n_live = cfg.max_frontier
            bucket = _bucket(n_live, cfg)
            valid, q_idx, codes = _compact(flat_mask, bucket, flat_q,
                                           flat_codes)
        return collide, c


def query_batched_scenes(octrees: List[Octree], obbs: OBBs,
                         config: EngineConfig = EngineConfig()
                         ) -> Tuple[np.ndarray, Counters]:
    """Traverse S scenes, each with its own (M,) OBB set, in ONE compiled call.

    ``obbs`` fields carry a leading scene axis: center (S, M, 3).  All trees
    must share a depth; node counts may differ arbitrarily.

    CSR modes (``wavefront_fused`` / ``wavefront_persistent``) run the
    **ragged flat frontier**: one pool of (scene, query, CSR node) triples
    over the :func:`repro.core.octree.concat_device_octrees` flat table —
    mixed-size scenes share the compiled call and the compaction pool, and
    no work scales with the largest scene's padding.  ``mode="wavefront"``
    (whose frontier carries Morton codes, not CSR indices) keeps the legacy
    padded-vmap path over :func:`stack_device_octrees` for A/B benchmarks.
    Returns ((S, M) verdicts, aggregate counters).

    Compatibility front-end over ``CollisionEngine(octrees).execute``; the
    device scene tables are memoized module-wide, so repeat calls on the
    same octree list skip the table build.
    """
    assert config.device_resident, "multi-scene batching needs a device mode"
    assert obbs.center.ndim == 3 and obbs.center.shape[0] == len(octrees)
    return CollisionEngine(list(octrees), config).execute(plan_scenes(obbs))
