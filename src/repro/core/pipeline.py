"""End-to-end motion-planning pipeline with the explicit collision gate.

RoboGPU Fig. 18: point-cloud processing (sampling + grouping) -> neural
planner rollout -> explicit collision check of the proposed trajectory.
The paper's safety argument is that the collision gate must be part of the
pipeline; with RoboCore-style acceleration it adds little to the critical
path.  :func:`plan_with_collision_gate` runs the MPiNets policy
(:mod:`repro.models.planner`) over a batch of problems, then the gate.

Every front-end here lowers through :mod:`repro.engine.plan` and executes
on :meth:`repro.engine.executor.CollisionEngine.execute` — host-loop and
device-resident engines consume the *same* plan, so there is no
per-front-end engine dispatch left in this module.  ``check_edges`` is
the swept-edge (CCD) workload: batched first-hit validation of planning
graph edges (see :mod:`repro.core.sweep`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.geometry import NUM_LINKS
from repro.core.sweep import sweep_edges
from repro.engine import CollisionEngine
from repro.engine.plan import plan_trajectory
from repro.models.planner import MpiNets, rollout, unnormalize


@dataclasses.dataclass
class PlannerStats:
    """What one planning call did (:func:`plan_with_collision_gate`)."""

    problems: int
    steps: int                  # rollout steps, each a whole forward pass
    fps_picks: int              # dependent FPS picks, per problem
    ball_fill: Tuple[float, ...]  # neighbours found / k, per set abstraction
    gate_obbs: int              # link OBBs the gate checked


@dataclasses.dataclass
class PipelineResult:
    trajectories: np.ndarray    # (B, T+1, 7) joint waypoints, radians
    normalized: np.ndarray      # (B, T+1, 7) the policy's joints in [-1, 1]
    colliding: np.ndarray       # (B, T+1) bool, per waypoint
    collision_free: np.ndarray  # (B,) bool
    stats: PlannerStats
    counters: Optional[object] = None


@dataclasses.dataclass
class EdgeCheckResult:
    """Batched swept-edge validation verdicts (``check_edges``)."""

    first_hit: np.ndarray   # (E,) float32 t0 of first colliding sub-interval
    #                         (inf = edge collision-free)
    collide: np.ndarray     # (E,) bool
    counters: Optional[object] = None


def check_trajectory(engine: CollisionEngine, waypoints: jax.Array,
                     base_pos=None):
    """FK every waypoint -> link OBBs -> octree collision query.

    ``waypoints`` is (T, 7); the trajectory lowers to one flat link-OBB
    plan whose un-flattening recipe ORs each waypoint's links — every
    engine mode consumes the same plan in a single call (device modes: one
    compiled call with per-query early exit).  Returns (per-waypoint
    collision flags, counters).
    """
    return engine.execute(plan_trajectory(waypoints, base_pos=base_pos))


def check_trajectories(engine: CollisionEngine, waypoints: jax.Array,
                       base_pos=None):
    """Collision-gate a whole batch of trajectories in one compiled call.

    ``waypoints`` is (B, T, 7); returns ((B, T) per-waypoint flags,
    counters).  This is the batched-throughput path of the collision gate:
    B * T waypoint queries traverse the octree together, each retiring from
    the wavefront as soon as its verdict is decided.
    """
    return engine.execute(plan_trajectory(waypoints, base_pos=base_pos))


def check_edges(engine: CollisionEngine, q_from: jax.Array, q_to: jax.Array,
                resolution: int = 16, base_pos=None,
                in_traversal_exit: bool = True) -> EdgeCheckResult:
    """Swept-edge (CCD) validation of E planning-graph edges.

    Each edge ``q_from[e] -> q_to[e]`` (joint space, linear interpolation)
    is enclosed in conservative swept OBBs and bisected only where the
    swept volume hits occupied leaves; the finest round's payload lane
    returns the per-edge *first* colliding sub-interval with in-traversal
    early exit (:mod:`repro.core.sweep`).  ``first_hit[e]`` is the start
    parameter t0 of that sub-interval (``inf`` for a collision-free edge),
    an upper-bound verdict over dense waypoint sampling at the same
    ``resolution``.  ``resolution`` must be a power of two (the bisection
    halves segments down to width 1).
    """
    first_hit, collide, counters = sweep_edges(
        engine, q_from, q_to, resolution=resolution, base_pos=base_pos,
        in_traversal_exit=in_traversal_exit)
    return EdgeCheckResult(first_hit=first_hit, collide=collide,
                           counters=counters)


_radians = jax.jit(unnormalize)


def plan_with_collision_gate(model: MpiNets, engine: CollisionEngine,
                             obstacles, q_start, q_goal) -> PipelineResult:
    """Plan B problems with the MPiNets policy and gate every waypoint.

    ``obstacles`` (B, obstacle_points, 3) are each problem's obstacle
    points, ``q_start`` and ``q_goal`` (B, 7) its joints in radians.  One
    jitted rollout (:func:`repro.models.planner.rollout`) re-encodes the
    cloud at every step; its (B, steps + 1) waypoints then go through the
    collision gate as one trajectory plan.  Spans ``pipeline.rollout`` and
    ``pipeline.gate`` (the engine's own spans inside it) time the two;
    ``counters`` come from the gate.
    """
    cfg = model.cfg
    B = int(np.shape(q_start)[0])
    with TraceAnnotation("pipeline.rollout", problems=B, steps=cfg.steps):
        q_norm, fills = rollout(model, jnp.asarray(obstacles, jnp.float32),
                                jnp.asarray(q_start, jnp.float32),
                                jnp.asarray(q_goal, jnp.float32))
        waypoints = _radians(q_norm)
        normalized, traj, fills = jax.device_get((q_norm, waypoints, fills))
    with TraceAnnotation("pipeline.gate", waypoints=traj.shape[1]):
        flags, counters = check_trajectories(engine, waypoints)
    flags = np.asarray(flags)
    enc = cfg.encoder
    per_sa = B * cfg.steps * np.array(
        [enc.sa1.centres * enc.sa1.neighbours,
         enc.sa2.centres * enc.sa2.neighbours])
    stats = PlannerStats(
        problems=B, steps=cfg.steps, fps_picks=cfg.fps_picks,
        ball_fill=tuple(float(f) for f in np.asarray(fills) / per_sa),
        gate_obbs=int(flags.size) * NUM_LINKS)
    return PipelineResult(trajectories=traj, normalized=normalized,
                          colliding=flags,
                          collision_free=~flags.any(axis=1), stats=stats,
                          counters=counters)
