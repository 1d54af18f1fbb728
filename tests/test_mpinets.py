"""MPiNets at the small preset, on seeded weights, against the plain
reference (``repro.models.mpinets_ref``): sampling and grouping exactly,
the joint step within float32 rounding, re-encoding at every step, and
the batched planning path against one problem at a time."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.octree import build_octree
from repro.core.pipeline import plan_with_collision_gate
from repro.data.robotics import PANDA_JOINT_HI, PANDA_JOINT_LO, make_scene
from repro.engine import CollisionEngine, EngineConfig
from repro.kernels.ballquery import ball_query
from repro.kernels.fps import fps_sample
from repro.models import mpinets_ref as ref
from repro.models.planner import (SMALL, init_mpinets, labels, normalize,
                                  robot_points, rollout, target_points)
from repro.models.pointnet import encode, group_all, group_norm

B = 3
#: Joint-step agreement with the reference, in normalised joint units.
#: Both sides are float32 with float32-accurate matmuls; they differ only
#: in the order of float32 sums (batched against one problem, fused
#: against not), a few ulps a layer through ten layers of steps of order
#: 0.1.  Computing the matmuls in bfloat16 instead misses by ~1e-2.
STEP_TOL = 2e-5


@pytest.fixture(scope="module")
def model():
    return init_mpinets(SMALL, seed=0)


@pytest.fixture(scope="module")
def problems():
    rng = np.random.default_rng(1)
    scene = make_scene("cubby", num_points=4096)
    obstacles = scene.points[rng.choice(len(scene.points),
                                        (B, SMALL.obstacle_points))]
    start = rng.uniform(PANDA_JOINT_LO, PANDA_JOINT_HI, (B, 7))
    goal = rng.uniform(PANDA_JOINT_LO, PANDA_JOINT_HI, (B, 7))
    return (scene, obstacles.astype(np.float32), start.astype(np.float32),
            goal.astype(np.float32))


@pytest.fixture(scope="module")
def planned(model, problems):
    _, obstacles, start, goal = problems
    traj, fills = rollout(model, obstacles, start, goal)
    return np.asarray(traj), np.asarray(fills)


def _cloud(model, obstacles, goal, q):
    return jnp.concatenate([robot_points(model, q), jnp.asarray(obstacles),
                            target_points(model, jnp.asarray(goal))], 1)


@pytest.mark.parametrize("which", ["sa1", "sa2"])
def test_sampling_and_grouping_match_reference(model, problems, planned,
                                               which):
    """A step's FPS indices and ball-query slots equal the reference's,
    every problem, every slot."""
    _, obstacles, _, goal = problems
    xyz = _cloud(model, obstacles, goal, jnp.asarray(planned[0][:, 2]))
    enc = SMALL.encoder
    if which == "sa2":
        xyz = jnp.take_along_axis(
            xyz, fps_sample(xyz, enc.sa1.centres)[..., None], axis=1)
    sa = getattr(enc, which)
    idx = np.asarray(fps_sample(xyz, sa.centres))
    centres = jnp.take_along_axis(xyz, jnp.asarray(idx)[..., None], axis=1)
    nbr, filled = ball_query(xyz, centres, sa.radius, sa.neighbours)
    for b in range(B):
        want = np.asarray(ref.fps(xyz[b], sa.centres))
        np.testing.assert_array_equal(idx[b], want)
        r_idx, r_filled = ref.ball_query(xyz[b], xyz[b][want], sa.radius,
                                         sa.neighbours)
        np.testing.assert_array_equal(np.asarray(nbr[b]), r_idx)
        np.testing.assert_array_equal(np.asarray(filled[b]), r_filled)
    assert 0 < int(np.asarray(filled).min())       # a centre finds itself


def test_rollout_steps_match_reference(model, problems, planned):
    """Every step of the batched rollout, teacher-forced, is the
    reference's step from the same joints."""
    _, obstacles, _, goal = problems
    traj, _ = planned
    target = target_points(model, jnp.asarray(goal))
    for t in range(SMALL.steps):
        q = jnp.asarray(traj[:, t])
        robot = robot_points(model, q)
        for b in range(B):
            want = ref.step(SMALL, model.params, robot[b], obstacles[b],
                            target[b], q[b])
            np.testing.assert_allclose(traj[b, t + 1], np.asarray(want),
                                       atol=STEP_TOL, rtol=0)


def test_rollout_reencodes_every_step(model, problems, planned):
    """The robot's points move with q, the encoding with them, and the
    rollout's step t is the step from the cloud at q_t, not at q_0."""
    _, obstacles, _, goal = problems
    traj, fills = planned
    q0, q2 = jnp.asarray(traj[:, 0]), jnp.asarray(traj[:, 2])
    assert float(jnp.abs(robot_points(model, q2)
                         - robot_points(model, q0)).max()) > 1e-2
    feats = jnp.broadcast_to(jnp.asarray(labels(SMALL)).T,
                             (B, 1, SMALL.points))
    encs = [encode(SMALL.encoder, model.params["pointnet"],
                   _cloud(model, obstacles, goal, q), feats,
                   jax.lax.Precision.HIGHEST)[0] for q in (q0, q2)]
    assert float(jnp.abs(encs[1] - encs[0]).max()) > 1e-3
    target = target_points(model, jnp.asarray(goal))
    stale = ref.step(SMALL, model.params, robot_points(model, q0)[0],
                     obstacles[0], target[0], q2[0])
    assert np.abs(np.asarray(stale) - traj[0, 3]).max() > 10 * STEP_TOL
    per_sa = np.array([SMALL.encoder.sa1.centres, SMALL.encoder.sa2.centres])
    assert (fills > 0).all() and (fills <= per_sa * 16 * B * SMALL.steps
                                  ).all()


def test_batched_plan_equals_single_problems(model, problems):
    """One call over B problems gives what B calls of one problem give:
    trajectories, per-waypoint verdicts and fills."""
    scene, obstacles, start, goal = problems
    engine = CollisionEngine(build_octree(scene.points, depth=4),
                             EngineConfig(mode="wavefront_persistent"))
    both = plan_with_collision_gate(model, engine, obstacles, start, goal)
    assert both.trajectories.shape == (B, SMALL.steps + 1, 7)
    assert both.colliding.shape == (B, SMALL.steps + 1)
    assert both.stats.gate_obbs == B * (SMALL.steps + 1) * 7
    assert both.stats.fps_picks == SMALL.steps * (32 - 1 + 8 - 1)
    np.testing.assert_allclose(both.normalized[:, 0], normalize(start),
                               atol=1e-6)
    for b in range(B):
        one = plan_with_collision_gate(model, engine, obstacles[b:b + 1],
                                       start[b:b + 1], goal[b:b + 1])
        np.testing.assert_allclose(one.normalized[0], both.normalized[b],
                                   atol=1e-6, rtol=0)
        np.testing.assert_array_equal(one.colliding[0], both.colliding[b])
        assert one.collision_free[0] == both.collision_free[b]
    assert both.counters.ref_arm_fallbacks == 0


def test_group_norm_matches_its_equation():
    """GroupNorm(16) of (B, C): per group (x - mean) / sqrt(var + 1e-5),
    the variance biased, then scale and bias per channel."""
    rng = np.random.default_rng(3)
    x = rng.normal(2.0, 3.0, (4, 256)).astype(np.float32)
    norm = {"scale": rng.normal(size=256).astype(np.float32),
            "bias": rng.normal(size=256).astype(np.float32)}
    got = np.asarray(group_norm(norm, jnp.asarray(x), 16))
    g = x.astype(np.float64).reshape(4, 16, 16)
    want = ((g - g.mean(-1, keepdims=True))
            / np.sqrt(g.var(-1, keepdims=True) + 1e-5)).reshape(4, 256)
    np.testing.assert_allclose(got, want * norm["scale"] + norm["bias"],
                               atol=1e-5, rtol=1e-5)


def test_group_all_matches_its_equation(model):
    """GroupAll: every point's [xyz, features] (xyz not centred) through
    the shared MLP, a bias and ReLU each layer, then the max over points."""
    rng = np.random.default_rng(4)
    xyz = rng.uniform(-1, 1, (2, 8, 3)).astype(np.float32)
    c2 = SMALL.encoder.sa2.widths[-1]
    feats = rng.normal(size=(2, 8, c2)).astype(np.float32)
    layers = model.params["pointnet"]["sa_global"]
    got = np.asarray(group_all(layers, jnp.asarray(xyz),
                               jnp.asarray(feats.transpose(0, 2, 1)),
                               jax.lax.Precision.HIGHEST))
    h = np.concatenate([xyz, feats], -1).astype(np.float64)
    for layer in layers:
        h = np.maximum(h @ np.asarray(layer["w"], np.float64)
                       + np.asarray(layer["b"], np.float64), 0.0)
    np.testing.assert_allclose(got, h.max(axis=1), atol=1e-5, rtol=1e-5)


def test_published_preset_widths():
    """The published preset: 6,272 points, ~19.1 M parameters, and the
    44,022 dependent FPS picks of a 69-step rollout."""
    from repro.models.planner import MPINETS
    shapes = jax.eval_shape(lambda: init_mpinets(MPINETS, 0))
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(shapes.params))
    assert MPINETS.points == 6272 and n == 19_068_103
    assert MPINETS.fps_picks == 44_022
    assert dataclasses.replace(MPINETS, precision="default") != MPINETS
