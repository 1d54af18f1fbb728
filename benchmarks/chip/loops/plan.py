"""Closed planning loop: one client plans a batch of problems with MPiNets,
gates every waypoint, waits for the verdicts, and sends the next batch,
back to back, cycling a pool of ``pool`` batches drawn at set-up.

One call is ``repro.core.pipeline.plan_with_collision_gate``: a jitted
rollout of ``problems`` problems, each step a whole forward pass over the
problem's 6,272-point cloud with the robot's points moved to the step's
joints, then one trajectory plan of every waypoint's link OBBs through
``CollisionEngine.execute`` against the configuration's scene.

Set-up builds the gate's engine from the run's scene stream
(:func:`collision.build`) and the policy from ``weights_seed``.  The pool
comes from ``fixed_work_seed`` alone, so every seed plans the same work:
each problem's obstacle points are drawn on the configuration's boxes,
and its start and goal uniform in the joint limits, redrawn until every
link is clear (:class:`reference.VoxelScene` of a cloud of the same boxes
from the same stream).  The run's seed orders the pool.  Every batch is
planned once before the window, so no program is lowered inside it.

The check (:meth:`Loop.check`) judges both halves of every call:

* the gate, as the collision loops judge it (:mod:`compare`): each
  waypoint's verdict against the float64 separation of its link OBBs
  (:mod:`fk`) from the voxelized scene; ``missed_m``, ``false_hit_m``;
* the policy: ``check_samples`` (call, problem, step) triples drawn from
  the run's sample stream; :mod:`mpinets_reference` recomputes each step
  from the program's joints at that step (teacher-forced) and the robot
  points the program's sampler places there, and ``policy_err`` is the
  largest absolute difference of the next joints, in normalised units;
* ``unanswered``: waypoints that came back without a verdict.

Limits: :data:`compare.LIMITS` for the gate, ``planner_limits.json`` for
the policy, each with its reason.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import numpy as np

import collision
import compare
import fk
import mpinets_reference
import reference
import scenes
from repro.core.pipeline import plan_with_collision_gate
from repro.models.planner import (config_from_dict, init_mpinets,
                                  robot_points, target_points)

HERE = Path(__file__).resolve().parent
LIMITS = json.loads((HERE.parent / "planner_limits.json").read_text())


def free_configs(rng: np.random.Generator, vox, n: int) -> np.ndarray:
    """``n`` joint vectors uniform in the limits with every link clear of
    ``vox``, (n, 7) float32."""
    out = []
    while len(out) < n:
        q = rng.uniform(fk.JOINT_LO, fk.JOINT_HI, (2 * n, fk.NUM_LINKS))
        sep = vox.separation(*fk.link_obbs(q)).reshape(-1, fk.NUM_LINKS)
        out.extend(q[(sep > 0).all(axis=1)])
    return np.asarray(out[:n], np.float32)


def pool(config: dict, mix: dict, rng_order: np.random.Generator):
    """``pool`` batches of ``problems`` (obstacles (P, B, N, 3), start and
    goal (P, B, 7)) from ``fixed_work_seed``, in the run's order."""
    rng = np.random.default_rng(mix["fixed_work_seed"])
    p, b = mix["pool"], mix["problems"]
    vox = reference.VoxelScene(
        scenes.surface_points(config["boxes"], config["num_points"], rng),
        config["depth"])
    n_obs = config["model"]["obstacle_points"]
    obstacles = np.stack([scenes.surface_points(config["boxes"], n_obs, rng)
                          for _ in range(p * b)]).reshape(p, b, n_obs, 3)
    start = free_configs(rng, vox, p * b).reshape(p, b, fk.NUM_LINKS)
    goal = free_configs(rng, vox, p * b).reshape(p, b, fk.NUM_LINKS)
    order = rng_order.permutation(p)
    return obstacles[order], start[order], goal[order]


def _numpy(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


class Loop:
    def __init__(self, run, config: dict, mix: dict, seed: int,
                 seconds: float, mark):
        rng_scene, rng_traffic, self.rng_sample, _ = collision.streams(seed)
        self.engine, self.vox, tree = collision.build(config, rng_scene)
        mark("scene")
        self.model = init_mpinets(config_from_dict(config["model"]),
                                  config["weights_seed"])
        self.mix = mix
        self.config_model = config["model"]
        self.pool = pool(config, mix, rng_traffic)
        mark("traffic")
        run.level_widths = [len(level.codes) for level in tree.levels]
        run.unit_obbs = (mix["problems"] * (self.model.cfg.steps + 1)
                         * fk.NUM_LINKS)
        run.planner_model = config["model"]
        run.planner_problems = mix["problems"]
        for i in range(mix["pool"]):
            self._plan(i)

    def _plan(self, i: int):
        obstacles, start, goal = (x[i] for x in self.pool)
        return plan_with_collision_gate(self.model, self.engine, obstacles,
                                        start, goal)

    def window(self, run, seconds: float) -> dict:
        calls = []
        k = 0
        t_begin = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                i = k % self.mix["pool"]
                with jax.profiler.TraceAnnotation("bench.execute"):
                    t0 = time.perf_counter()
                    res = self._plan(i)
                    t1 = time.perf_counter()
                calls.append((i, t0, t1, res))
                k += 1
                if t1 - t_begin >= seconds:
                    break
        self.calls = calls
        run.window_s = calls[-1][2] - t_begin
        run.calls = [(t0, t1) for _, t0, t1, _ in calls]
        stats = [r.stats for *_, r in calls]
        return {"attempted": len(calls), "failed": 0,
                "engine": collision.engine_totals(
                    [r.counters for *_, r in calls]),
                "planner": {"problems": stats[0].problems,
                            "steps": stats[0].steps,
                            "fps_picks": stats[0].fps_picks,
                            "ball_fill": np.mean([s.ball_fill for s in stats],
                                                 axis=0).tolist(),
                            "gate_obbs": stats[0].gate_obbs}}

    def release(self):
        """Keep what the policy check needs of the program (its weights,
        the robot and target points its sampler places at the sampled
        steps), then free the program's state."""
        b, steps = self.mix["problems"], self.model.cfg.steps
        n = self.mix["check_samples"]
        self.samples = np.stack([
            self.rng_sample.integers(0, len(self.calls), n),
            self.rng_sample.integers(0, b, n),
            self.rng_sample.integers(0, steps, n)], 1)
        q = np.stack([self.calls[c][3].normalized[p, t]
                      for c, p, t in self.samples])
        goal = np.stack([self.pool[2][self.calls[c][0], p]
                         for c, p, _ in self.samples])
        self.robot = np.asarray(jax.jit(robot_points)(self.model, q))
        self.target = np.asarray(jax.jit(target_points)(self.model, goal))
        self.weights = _numpy(self.model.params)
        del self.engine, self.model

    def check(self) -> dict:
        vals = {"missed_m": 0.0, "false_hit_m": 0.0}
        seps: dict = {}
        unanswered = 0
        for _, _, _, res in self.calls:
            wps = res.trajectories
            key = wps.tobytes()
            if key not in seps:
                sep = self.vox.separation(*fk.link_obbs(wps.reshape(-1, 7)))
                seps[key] = sep.reshape(wps.shape[:2] + (-1,)).min(axis=-1)
            got = compare.readings(res.colliding.reshape(-1),
                                   seps[key].reshape(-1))
            vals = {k: max(vals[k], got[k]) for k in vals}
            unanswered += wps.shape[0] * wps.shape[1] - res.colliding.size
        err = 0.0
        for (c, p, t), robot, target in zip(self.samples, self.robot,
                                            self.target):
            i, res = self.calls[c][0], self.calls[c][3]
            want = mpinets_reference.step(
                self.config_model, self.weights, robot, self.pool[0][i, p],
                target, res.normalized[p, t])
            err = max(err, float(np.abs(want - res.normalized[p, t + 1])
                                 .max()))
        out = {k: [vals[k], compare.LIMITS[k]] for k in vals}
        out["policy_err"] = [err, LIMITS["policy_err"]["limit"]]
        out["unanswered"] = [unanswered, compare.LIMITS["unanswered"]]
        return out
