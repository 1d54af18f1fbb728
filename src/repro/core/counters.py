"""Work model for the collision engine.

The paper evaluates RoboCore in a cycle-level simulator; on CPU we cannot
measure TPU cycles, so every engine variant reports *architecture-neutral work
counters* next to wall clock: axis tests executed (what a conditional-return
machine runs) vs decoded (what predication still pays for), sphere tests,
nodes traversed per level, exit-code histogram, modeled bytes moved
(fused VMEM-resident kernel vs unfused HBM-materialized stages), and the
Mochi-style shader-handoff overhead.

Bytes model (f32):
  OBB record 60 B, AABB 24 B, staged intermediates (t,R,absR,halves) 108 B,
  margins 15*4 B, result 4 B.
  unfused test  = 84 (boxes) + 2*108 (terms round trip) + 2*60 (margins) + 4
                = 424 B
  fused test    = 84 + 8 (result+exit code)              = 92 B
  shader handoff (Mochi) = 128 B per reported hit.

Fused traversal step (kernels/traverse, ``mode="wavefront_fused"``): the
whole level is one kernel, so per live (query, node) pair per level the
HBM-resident traffic reduces to frontier-in / frontier-out:
  frontier triple in  (q_idx, Morton code, CSR node index)   = 12 B
  node metadata gather (full flag, child_start, child_mask)  = 12 B
  packed verdict word out (collide | is_term | exit_code)    =  4 B
  compacted next-frontier triple out (amortized, <= 1 slot
  per surviving pair per level)                              = 12 B
  fused step                                                 = 40 B
The query OBB table streams HBM->VMEM once per level and is amortized
across the whole frontier, so it does not appear in the per-pair cost —
exactly the paper's "intermediates never leave the unit" discipline.  The
unfused device arm instead materializes ~5 capacity-sized arrays per level
(4-field SactResult, searchsorted probe vectors, 8x-expanded candidate
codes, compaction scratch), which the 424 B/test figure models.

Persistent megakernel (kernels/persist, ``mode="wavefront_persistent"``):
the WHOLE traversal is one kernel and the frontier lives in VMEM for its
entire life, so HBM-resident frontier traffic collapses from
40 B/pair/level to a per-QUERY cost paid once:
  seed (query, root) pair in                                 = 12 B
  packed verdict word out                                    =  4 B
  per-query cost                                             = 16 B
(children past a tile's VMEM frontier are dropped and counted, never
written to HBM; the escalation replay re-reads the seeds).
Under the RESIDENT metadata layout the node-metadata and OBB tables
stream HBM->VMEM once per *kernel* (not per level), amortized across
every pair of every level — the closest TPU analogue of the paper's
conditional returns never leaving the core.  Under the STREAMED layout
(scenes past the VMEM residency budget, DESIGN.md §3) the metadata table
stays in HBM and each query tile double-buffers per-level row windows
instead; that traffic is explicit, not amortized, and priced at the
metadata row FORMAT's packed width (repro.core.quantize):
  per fetched fp32 row ([code, full, start, mask] int32)     = 16 B
  per fetched bf16 row (topo word + 10-bit fixed-point xyz)  =  8 B
  per fetched u8 row (single topo+octant word)               =  4 B
``Counters.meta_rows_streamed`` counts the rows the window schedule
fetched (level extents rounded up to whole DMA chunks, once per tile per
level the tile's frontier visits; 0 under the resident layout) — the row
COUNT is format-independent, so compression divides the streamed bytes by
exactly 2x/4x.  ``BYTES_META_STREAM`` / ``BYTES_META_STREAM_BF16`` /
``BYTES_META_STREAM_U8`` price the rows, and the product lands in
``Counters.meta_bytes_streamed``.

Payload lanes (swept-edge / first-hit plans, see ``repro.engine.plan``):
a grouped plan carries extra int32 lanes per query slot — the owner lane
(verdict-group id) and/or the payload lane (sub-interval rank) — that the
traversal gathers per frontier pair and folds into the per-group ``best``
with a min.  Each carried lane is modeled as ``BYTES_PAYLOAD_LANE`` extra
bytes per pair per level for the per-level arms, and per seed for the
persistent megakernel (the lanes ride the seed in and the best word out
replaces the boolean verdict word at equal width).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

BYTES_UNFUSED_TEST = 424
BYTES_FUSED_TEST = 92
BYTES_FUSED_STEP = 40
BYTES_PERSIST_QUERY = 16
BYTES_META_STREAM = 16
BYTES_META_STREAM_BF16 = 8
BYTES_META_STREAM_U8 = 4
BYTES_PAYLOAD_LANE = 4
BYTES_SHADER_HANDOFF = 128
NUM_EXIT_CODES = 18


@dataclasses.dataclass
class Counters:
    """Aggregate work counters for one engine invocation."""

    num_queries: int = 0
    nodes_traversed: int = 0            # (query, node) pairs tested
    nodes_per_level: List[int] = dataclasses.field(default_factory=list)
    leaf_tests: int = 0                 # tests against terminal (leaf/full) nodes
    axis_tests_executed: int = 0        # conditional-return work model
    axis_tests_decoded: int = 0         # predication / no-exit work model
    sphere_tests: int = 0
    exit_histogram: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(NUM_EXIT_CODES, np.int64))
    shader_invocations: int = 0
    bytes_moved: int = 0
    frontier_overflow: int = 0          # entries dropped at capacity (should be 0)
    escalations: int = 0                # overflow replays before a clean run
    meta_rows_streamed: int = 0         # HBM metadata rows DMA'd (streamed layout)
    meta_bytes_streamed: int = 0        # rows x the format's packed row width
    meta_gathers: int = 0               # persistent megakernel's metadata
    #                                     gather products (MXU passes): one
    #                                     per (tile, chunk, window) holding a
    #                                     lane at a level; depends on how the
    #                                     pool is cut into tiles and windows
    pad_queries: int = 0                # dead pool slots added by sharding /
    #                                     batch coalescing (zero work each —
    #                                     the live-prefix num_valid lane masks
    #                                     them — but they occupy pool width)
    ref_arm_fallbacks: int = 0          # persistent-mode plans the executor
    #                                     routed to the jnp ref arm instead of
    #                                     the Pallas kernel (capability gap,
    #                                     e.g. an owner group past MAX_TILE_BQ;
    #                                     each is also logged with the plan
    #                                     shape — MUST stay 0 in the kernel
    #                                     figure benches)
    # Service reliability counters (DESIGN.md §7): accumulated by the
    # RequestBatcher, reported in the fig_serve SLO rows.
    rejected: int = 0                   # shed at admission (malformed plan,
    #                                     full queue, or submit after close)
    retried: int = 0                    # transient-failure launch retries
    deadline_missed: int = 0            # failed pre-launch: deadline unmeetable
    launch_splits: int = 0              # bisect-retry splits isolating a
    #                                     poisoned request from co-riders
    worker_restarts: int = 0            # watchdog-detected worker deaths
    reshards: int = 0                   # device-loss recoveries: sharded
    #                                     launches re-sharded over the
    #                                     surviving device set and relaunched
    shards_lost: int = 0                # shard devices dropped from the
    #                                     collision mesh by those recoveries
    shard_rescales: int = 0             # elastic-width changes the batcher
    #                                     applied between launches (queue
    #                                     depth / p99 drifted past the SLO)
    degraded_launches: int = 0          # launches served in declared
    #                                     degraded mode (halved pad bucket,
    #                                     capped max_depth) instead of shed
    wall_time_s: float = 0.0

    def merge_exit_codes(self, codes: np.ndarray, valid: np.ndarray) -> None:
        hist = np.bincount(codes[valid].astype(np.int64),
                           minlength=NUM_EXIT_CODES)
        self.exit_histogram[:len(hist)] += hist

    def as_dict(self) -> Dict[str, object]:
        d = dataclasses.asdict(self)
        d["exit_histogram"] = self.exit_histogram.tolist()
        return d

    def merge(self, other: "Counters") -> None:
        """Accumulate another invocation's work into this one (batched
        front-end; wall clock is owned by the caller and left untouched)."""
        self.num_queries += other.num_queries
        self.nodes_traversed += other.nodes_traversed
        self.leaf_tests += other.leaf_tests
        self.axis_tests_executed += other.axis_tests_executed
        self.axis_tests_decoded += other.axis_tests_decoded
        self.sphere_tests += other.sphere_tests
        self.shader_invocations += other.shader_invocations
        self.bytes_moved += other.bytes_moved
        self.frontier_overflow += other.frontier_overflow
        self.escalations += other.escalations
        self.meta_rows_streamed += other.meta_rows_streamed
        self.meta_bytes_streamed += other.meta_bytes_streamed
        self.meta_gathers += other.meta_gathers
        self.pad_queries += other.pad_queries
        self.ref_arm_fallbacks += other.ref_arm_fallbacks
        self.rejected += other.rejected
        self.retried += other.retried
        self.deadline_missed += other.deadline_missed
        self.launch_splits += other.launch_splits
        self.worker_restarts += other.worker_restarts
        self.reshards += other.reshards
        self.shards_lost += other.shards_lost
        self.shard_rescales += other.shard_rescales
        self.degraded_launches += other.degraded_launches
        self.exit_histogram += other.exit_histogram
        a, b = self.nodes_per_level, other.nodes_per_level
        self.nodes_per_level = [
            (a[i] if i < len(a) else 0) + (b[i] if i < len(b) else 0)
            for i in range(max(len(a), len(b)))]

    def early_exit_fraction(self, half: int = 7) -> float:
        """Fraction of tests that terminate within ``half`` axis tests.

        Paper §I: "around 60% of collision queries can be terminated early
        after less than half of the total tests".
        """
        total = int(self.exit_histogram.sum())
        if total == 0:
            return 0.0
        # sphere exits (codes 0,1) + axis exits with index < half
        early = int(self.exit_histogram[0] + self.exit_histogram[1]
                    + self.exit_histogram[2:2 + half].sum())
        return early / total
