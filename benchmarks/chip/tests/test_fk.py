"""The benchmark's numpy FK against the program's jnp FK."""
import chipbench_paths  # noqa: F401  (first: the import path)
import jax.numpy as jnp
import numpy as np

import fk
from repro.core.geometry import arm_link_obbs, trajectory_obbs
from repro.data.robotics import PANDA_JOINT_HI, PANDA_JOINT_LO


def test_joint_limits_match_program():
    np.testing.assert_array_equal(fk.JOINT_LO.astype(np.float32),
                                  PANDA_JOINT_LO)
    np.testing.assert_array_equal(fk.JOINT_HI.astype(np.float32),
                                  PANDA_JOINT_HI)


def test_link_obbs_match_program_fk():
    q = np.random.default_rng(0).uniform(fk.JOINT_LO, fk.JOINT_HI,
                                         (2000, 7)).astype(np.float32)
    c, h, r = fk.link_obbs(q, base=(0.1, -0.2, 0.05))
    ref = arm_link_obbs(jnp.asarray(q), base_pos=jnp.asarray(
        [0.1, -0.2, 0.05], jnp.float32))
    # float32 FK through 7 joints of ~0.3 m links: a few ulps of 1 m.
    np.testing.assert_allclose(c, np.asarray(ref.center), rtol=0, atol=2e-6)
    np.testing.assert_array_equal(h, np.asarray(ref.half))
    np.testing.assert_allclose(r, np.asarray(ref.rot), rtol=0, atol=2e-6)


def test_straight_segments_match_program_trajectory():
    rng = np.random.default_rng(1)
    start, end = fk.segments(rng, 1, {"kind": "uniform"})
    c, _, r = fk.link_obbs(fk.waypoints(start, end, 60))
    ref = trajectory_obbs(jnp.asarray(start[0], jnp.float32),
                          jnp.asarray(end[0], jnp.float32), 60)
    np.testing.assert_allclose(c, np.asarray(ref.center), rtol=0, atol=2e-6)
    np.testing.assert_allclose(r, np.asarray(ref.rot), rtol=0, atol=2e-6)


def test_offset_goal_stays_in_limits_and_near_start():
    start, end = fk.segments(np.random.default_rng(2), 5000,
                             {"kind": "offset", "rad": 0.3})
    assert (end >= fk.JOINT_LO).all() and (end <= fk.JOINT_HI).all()
    assert np.abs(end - start).max() <= 0.3
