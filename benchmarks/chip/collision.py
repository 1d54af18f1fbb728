"""What the collision loops (``loops/closed.py``, ``loops/open.py``) share:
the run's random streams, the scene and its engine, the plan of a unit,
and the engine counters a result reports.
"""
from __future__ import annotations

import numpy as np

import reference
import scenes
from repro.core.geometry import OBBs
from repro.core.octree import build_octree
from repro.engine import CollisionEngine, EngineConfig, plan_queries

#: Engine counters a result reports, summed over the window (the batcher's
#: totals include its warm-up request).
ENGINE_FIELDS = ("nodes_traversed", "escalations", "ref_arm_fallbacks",
                 "frontier_overflow", "pad_queries", "rejected")


def streams(seed: int):
    """The run's four independent streams, in this order: scene, traffic,
    sample, warm-up."""
    seq = np.random.SeedSequence(seed % 2**64)
    return tuple(np.random.default_rng(s) for s in seq.spawn(4))


def build(config: dict, rng: np.random.Generator):
    """The scene's cloud from the seed, its octree and engine, and the
    reference's voxel grid of the same cloud."""
    points = scenes.surface_points(config["boxes"], config["num_points"],
                                   rng)
    tree = build_octree(points, depth=config["depth"])
    engine = CollisionEngine(tree, EngineConfig(**config["engine"]))
    return engine, reference.VoxelScene(points, config["depth"]), tree


def plan(c, h, r):
    return plan_queries(OBBs(center=c, half=h, rot=r))


def engine_totals(counters) -> dict:
    return {f: sum(getattr(c, f) for c in counters) for f in ENGINE_FIELDS}
