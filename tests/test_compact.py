"""Stream compaction vs a numpy oracle on empty/full/ragged masks."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.compact.ops import compact_pairs, stream_compact
from repro.kernels.compact.ref import compact_ref


def _oracle(mask, vals, n_out):
    packed = np.asarray(vals)[np.asarray(mask)]
    return min(len(packed), n_out), packed[:n_out]


def _check(mask, vals, n_out, **kw):
    cnt, out = stream_compact(jnp.asarray(mask), jnp.asarray(vals), n_out,
                              **kw)
    exp_cnt, exp = _oracle(mask, vals, n_out)
    assert int(cnt) == exp_cnt
    assert (np.asarray(out)[:exp_cnt] == exp).all()


@pytest.mark.parametrize("n", [700, 1, 257])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_compact_masks(n, density):
    rs = np.random.RandomState(0)
    n_out = 512
    mask = rs.rand(n) < density
    vals = rs.randint(0, 1 << 30, (n, 2)).astype(np.int32)
    _check(mask, vals, n_out)


@pytest.mark.parametrize("n_out", [64, 299])
def test_compact_overflow_drops_highest(n_out):
    """Survivors past n_out are the highest input indices; they drop."""
    n = 300
    mask = np.ones(n, bool)
    vals = np.arange(n, dtype=np.int32)[:, None]
    cnt, out = stream_compact(jnp.asarray(mask), jnp.asarray(vals), n_out)
    assert int(cnt) == n_out
    assert (np.asarray(out)[:, 0] == np.arange(n_out)).all()


@pytest.mark.parametrize("n", [1, 255, 256, 257, 1000])
def test_compact_ragged_sizes_match_oracle(n):
    rs = np.random.RandomState(n)
    mask = rs.rand(n) < 0.5
    vals = rs.randint(0, 1 << 30, (n, 2)).astype(np.int32)
    n_out = 256
    c_ref, o_ref = compact_ref(jnp.asarray(mask), jnp.asarray(vals), n_out)
    exp_cnt, exp = _oracle(mask, vals, n_out)
    assert int(c_ref) == exp_cnt
    assert (np.asarray(o_ref)[:exp_cnt] == exp).all()


def test_compact_pairs_roundtrips_uint32():
    rs = np.random.RandomState(7)
    n = 500
    mask = rs.rand(n) < 0.4
    q = rs.randint(0, 1 << 20, n).astype(np.int32)
    codes = rs.randint(0, 1 << 30, n).astype(np.uint32)
    cnt, q_out, c_out = compact_pairs(jnp.asarray(mask), jnp.asarray(q),
                                      jnp.asarray(codes), 1024)
    k = int(cnt)
    assert (np.asarray(q_out)[:k] == q[mask][:k]).all()
    assert (np.asarray(c_out)[:k] == codes[mask][:k]).all()
    assert np.asarray(c_out).dtype == np.uint32
