"""End-to-end example: behaviour-clone the MPiNets policy (small preset) and
plan WITH the explicit collision gate (the paper's full pipeline).

    PYTHONPATH=src python examples/train_planner.py            # ~1 min CPU
    PYTHONPATH=src python examples/train_planner.py --steps 300

Stages:
  1. Build a synthetic Cubby scene + octree (repro.data.robotics).
  2. Generate expert steps (goal-seeking with collision-aware rejection)
     and behaviour-clone the policy on (cloud at q, q) -> normalised step.
  3. Plan a batch of problems; every waypoint passes through the explicit
     collision gate (core/pipeline.py) — the paper's safety argument.

The published widths (``MPINETS``) train the same way; on the CPU they
are slow, so this example uses the ``SMALL`` preset.
"""
import argparse
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.octree import build_octree
from repro.core.pipeline import check_trajectory, plan_with_collision_gate
from repro.data.robotics import PANDA_JOINT_HI, PANDA_JOINT_LO, make_scene
from repro.engine import CollisionEngine, EngineConfig
from repro.models.planner import (SMALL, bc_loss, init_mpinets, normalize,
                                  target_points)
from repro.train.optimizer import OptConfig, adamw_update, init_opt_state


def make_expert_data(engine, n_episodes, steps, rs):
    """Greedy goal-seeking expert with collision-aware step rejection:
    (q, goal, step) in radians."""
    lo, hi = PANDA_JOINT_LO, PANDA_JOINT_HI
    qs, goals, deltas = [], [], []
    for _ in range(n_episodes):
        q = rs.uniform(lo, hi).astype(np.float32)
        goal = rs.uniform(lo, hi).astype(np.float32)
        for _ in range(steps):
            step_v = np.clip(goal - q, -0.4, 0.4)
            cand = q + step_v
            flags, _ = check_trajectory(engine, jnp.asarray(cand[None]))
            if bool(np.asarray(flags)[0]):
                # collision: deflect with a random detour step
                step_v = rs.uniform(-0.3, 0.3, 7).astype(np.float32)
                cand = np.clip(q + step_v, lo, hi)
            qs.append(q.copy())
            goals.append(goal.copy())
            deltas.append((cand - q).astype(np.float32))
            q = cand
    return np.stack(qs), np.stack(goals), np.stack(deltas)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    rs = np.random.RandomState(0)
    print("building scene + octree ...")
    scene = make_scene("cubby", num_points=65536)
    engine = CollisionEngine(build_octree(scene.points, depth=6),
                             EngineConfig(mode="wavefront_fused"))

    print("generating expert data ...")
    qs, goals, deltas = make_expert_data(engine, 6, 20, rs)
    n = len(qs)
    print(f"  {n} expert tuples")

    model = init_mpinets(SMALL, seed=0)
    print(f"planner params: {model.num_params / 1e3:.0f}k")
    span = PANDA_JOINT_HI - PANDA_JOINT_LO
    opt_cfg = OptConfig(lr=3e-4, warmup_steps=10, total_steps=args.steps,
                        weight_decay=0.01)
    params, opt_state = model.params, init_opt_state(model.params, opt_cfg)
    B = 16
    loss_grad = jax.jit(jax.value_and_grad(
        lambda p, obs, tgt, q, d: bc_loss(model, p, obs, tgt, q, d)))

    t0 = time.time()
    for step in range(args.steps):
        idx = rs.randint(0, n, B)
        obs = scene.points[rs.choice(len(scene.points),
                                     (B, SMALL.obstacle_points))]
        tgt = target_points(model, jnp.asarray(goals[idx]))
        loss, grads = loss_grad(params, jnp.asarray(obs), tgt,
                                normalize(jnp.asarray(qs[idx])),
                                jnp.asarray(2.0 * deltas[idx] / span))
        params, opt_state, _ = adamw_update(params, grads, opt_state,
                                            opt_cfg)
        if step % max(args.steps // 10, 1) == 0:
            print(f"step {step:4d}  bc-loss {float(loss):.4f}  "
                  f"({time.time()-t0:.0f}s)")

    print("\nplanning 8 problems with the explicit collision gate ...")
    model = dataclasses.replace(model, params=params)
    obs = scene.points[rs.choice(len(scene.points),
                                 (8, SMALL.obstacle_points))]
    start = rs.uniform(PANDA_JOINT_LO, PANDA_JOINT_HI, (8, 7))
    goal = rs.uniform(PANDA_JOINT_LO, PANDA_JOINT_HI, (8, 7))
    res = plan_with_collision_gate(model, engine, obs, start, goal)
    reached = np.linalg.norm(res.trajectories[:, -1] - goal, axis=1) < 0.5
    for b in range(8):
        print(f"  problem {b}: reached={reached[b]} "
              f"collision_free={res.collision_free[b]}")
    print(f"\nsuccess(collision-free & reached)="
          f"{int((reached & res.collision_free).sum())}/8; unsafe plans "
          f"caught by the gate={int((~res.collision_free).sum())}/8")


if __name__ == "__main__":
    main()
