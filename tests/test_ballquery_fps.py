"""Ball query (P-Ray == P-Sphere == brute force) and FPS invariants."""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import seeded_property

from repro.core.ballquery import (ball_query_pray, ball_query_psphere,
                                  ball_query_ref)
from repro.core.octree import build_octree
from repro.kernels.fps import fps_sample


def _sets(idx, cnt):
    idx, cnt = np.asarray(idx), np.asarray(cnt)
    return [set(idx[m][:cnt[m]].tolist()) for m in range(len(cnt))]


@pytest.mark.parametrize("r,k", [(0.15, 8), (0.3, 32)])
def test_psphere_and_pray_match_bruteforce(r, k):
    rs = np.random.RandomState(0)
    pts = rs.uniform(-1, 1, (3000, 3)).astype(np.float32)
    qs = rs.uniform(-1, 1, (48, 3)).astype(np.float32)
    ref_idx, ref_cnt = ball_query_ref(jnp.asarray(pts), jnp.asarray(qs), r, k)
    tree = build_octree(pts, depth=5)
    ps_idx, ps_cnt, _ = ball_query_psphere(tree, jnp.asarray(qs), r, k)
    pr_idx, pr_cnt, _ = ball_query_pray(jnp.asarray(pts), jnp.asarray(qs), r,
                                        k, depth=3)
    ref_cnt = np.asarray(ref_cnt)
    assert (np.asarray(ps_cnt) == ref_cnt).all()
    assert (np.asarray(pr_cnt) == ref_cnt).all()
    d2 = ((qs[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    for m in range(48):
        true_set = set(np.nonzero(d2[m] <= r * r)[0].tolist())
        for got in (_sets(ps_idx, ps_cnt)[m], _sets(pr_idx, pr_cnt)[m]):
            if ref_cnt[m] < k:
                assert got == true_set
            else:
                assert got <= true_set and len(got) == k


def test_psphere_early_exit_saves_nodes_and_preserves_counts():
    rs = np.random.RandomState(1)
    pts = rs.uniform(-1, 1, (40000, 3)).astype(np.float32)
    tree = build_octree(pts, depth=6)
    qs = jnp.asarray(rs.uniform(-0.8, 0.8, (64, 3)).astype(np.float32))
    _, c_ee_cnt, c_ee = ball_query_psphere(tree, qs, 0.3, 8, early_exit=True)
    _, c_ne_cnt, c_ne = ball_query_psphere(tree, qs, 0.3, 8, early_exit=False)
    assert (np.asarray(c_ee_cnt) == np.asarray(c_ne_cnt)).all()
    assert c_ee.nodes_traversed < c_ne.nodes_traversed


def _ballquery_property(seed):
    rs = np.random.RandomState(seed % 100000)
    pts = rs.uniform(-1, 1, (500, 3)).astype(np.float32)
    qs = rs.uniform(-1, 1, (8, 3)).astype(np.float32)
    r, k = float(rs.uniform(0.05, 0.5)), int(rs.randint(1, 16))
    tree = build_octree(pts, depth=4)
    idx, cnt, _ = ball_query_psphere(tree, jnp.asarray(qs), r, k)
    idx, cnt = np.asarray(idx), np.asarray(cnt)
    d2 = ((qs[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    for m in range(8):
        sel = idx[m][:cnt[m]]
        assert (d2[m][sel] <= r * r + 1e-6).all()       # all within radius
        true_n = int((d2[m] <= r * r).sum())
        assert cnt[m] == min(true_n, k)                 # exact counts


@seeded_property(max_examples=10)
def test_ballquery_property_random(seed):
    """Hypothesis when available; deterministic fixed seeds otherwise —
    either way the property runs and the tier-1 suite reports 0 skipped."""
    _ballquery_property(seed)


def _spread(pts, idx):
    """Mean distance from every point to its nearest sample: lower is
    better coverage."""
    d2 = ((pts[:, None, :] - pts[idx][None, :, :]) ** 2).sum(-1)
    return float(np.sqrt(d2.min(axis=1)).mean())


def test_fps_invariants():
    rs = np.random.RandomState(2)
    pts = rs.uniform(-1, 1, (2000, 3)).astype(np.float32)
    idx_np = np.asarray(fps_sample(jnp.asarray(pts[None]), 64))[0]
    assert idx_np[0] == 0
    assert len(set(idx_np.tolist())) == 64              # distinct points
    # FPS spread beats random sampling (coverage metric, averaged seeds)
    rnd = [_spread(pts, np.random.RandomState(s).choice(2000, 64, False))
           for s in range(5)]
    assert _spread(pts, idx_np) < np.mean(rnd)


def test_fps_matches_numpy_oracle():
    rs = np.random.RandomState(3)
    pts = rs.uniform(-1, 1, (300, 3)).astype(np.float32)
    got = np.asarray(fps_sample(jnp.asarray(pts[None]), 20))[0]
    dist = np.full(300, np.inf, np.float32)
    idx = [0]
    for _ in range(19):
        d = pts - pts[idx[-1]]
        dist = np.minimum(dist, (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1])
                          + d[:, 2] * d[:, 2])
        idx.append(int(dist.argmax()))
    assert (got == np.asarray(idx)).all()
