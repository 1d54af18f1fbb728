"""Per-kernel interpret-mode validation vs pure-jnp oracles (shape sweeps)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.geometry import random_aabbs, random_obbs


@pytest.mark.parametrize("M,N,bm,bn,sph", [
    (64, 100, 32, 32, False),
    (130, 257, 64, 128, False),
    (8, 8, 8, 8, False),
    (100, 64, 32, 32, True),
])
def test_sact_kernel(M, N, bm, bn, sph):
    from repro.kernels.sact.ops import sact_fused_boxes
    from repro.kernels.sact.ref import sact_ref
    obbs = random_obbs(jax.random.PRNGKey(M + N), M)
    aabbs = random_aabbs(jax.random.PRNGKey(M * N), N)
    col_k, ec_k = sact_fused_boxes(obbs, aabbs, bm=bm, bn=bn,
                                   use_spheres=sph, interpret=True)
    col_r, ec_r = sact_ref(obbs.center, obbs.half, obbs.rot, aabbs.center,
                           aabbs.half, use_spheres=sph)
    assert bool(jnp.all(col_k == col_r))
    assert bool(jnp.all(ec_k == ec_r))


@pytest.mark.parametrize("M,N,r,k", [(70, 1000, 0.3, 16), (33, 500, 0.5, 4),
                                     (16, 128, 0.2, 8)])
def test_ballquery_kernel(M, N, r, k):
    """The batched kernel's slots and fills equal the brute-force oracle's,
    per problem, whether or not a ball overflows its k slots."""
    from repro.kernels.ballquery.ops import ball_query
    from repro.kernels.ballquery.ref import ball_query_ref
    rs = np.random.RandomState(M)
    pts = jnp.asarray(rs.uniform(-1, 1, (2, N, 3)).astype(np.float32))
    qs = jnp.asarray(rs.uniform(-1, 1, (2, M, 3)).astype(np.float32))
    idx_k, cnt_k = ball_query(pts, qs, r, k)
    for b in range(2):
        idx_r, cnt_r = ball_query_ref(pts[b], qs[b], r, k)
        assert bool(jnp.all(cnt_k[b] == cnt_r))
        assert bool(jnp.all(idx_k[b] == idx_r))  # same first-k order


@pytest.mark.parametrize("N,m,B", [(1000, 33, 3), (513, 16, 9)])
def test_fps_kernel(N, m, B):
    """Every pick of every problem (9 problems: two blocks of 8) equals the
    brute-force oracle's."""
    from repro.kernels.fps.ops import fps_sample
    from repro.kernels.fps.ref import fps_ref
    rs = np.random.RandomState(N)
    pts = jnp.asarray(rs.uniform(-1, 1, (B, N, 3)).astype(np.float32))
    got = fps_sample(pts, m)
    for b in range(B):
        assert bool(jnp.all(got[b] == fps_ref(pts[b], m)))


@pytest.mark.parametrize("BH,T,D,chunk", [(3, 70, 16, 16), (2, 64, 32, 32),
                                          (1, 33, 8, 8)])
def test_wkv6_kernel(BH, T, D, chunk):
    from repro.kernels.wkv6.ops import wkv6
    from repro.kernels.wkv6.ref import wkv6_ref
    rs = np.random.RandomState(T)
    mk = lambda s=0.5: jnp.asarray(
        rs.normal(size=(BH, T, D)).astype(np.float32)) * s
    r, k, v = mk(), mk(), mk(1.0)
    logw = -jnp.asarray(rs.uniform(0.01, 3.0, (BH, T, D)).astype(np.float32))
    u = jnp.asarray(rs.normal(size=(D,)).astype(np.float32)) * 0.3
    o_k, s_k = wkv6(r, k, v, logw, u, chunk=chunk)
    o_r, s_r = wkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,d,causal", [
    (2, 4, 2, 64, 64, 32, True),
    (1, 8, 8, 100, 100, 16, True),
    (2, 4, 1, 40, 72, 32, False),
])
def test_flash_attention_kernel(B, Hq, Hkv, Tq, Tk, d, causal):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    rs = np.random.RandomState(B * Tq)
    q = jnp.asarray(rs.normal(size=(B, Hq, Tq, d)).astype(np.float32))
    k = jnp.asarray(rs.normal(size=(B, Hkv, Tk, d)).astype(np.float32))
    v = jnp.asarray(rs.normal(size=(B, Hkv, Tk, d)).astype(np.float32))
    o_k = flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    o_r = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)


def test_wkv6_bf16_dtype():
    from repro.kernels.wkv6.ops import wkv6
    from repro.kernels.wkv6.ref import wkv6_ref
    rs = np.random.RandomState(9)
    BH, T, D = 2, 32, 16
    r = jnp.asarray(rs.normal(size=(BH, T, D)), jnp.bfloat16) * 0.5
    k = jnp.asarray(rs.normal(size=(BH, T, D)), jnp.bfloat16) * 0.5
    v = jnp.asarray(rs.normal(size=(BH, T, D)), jnp.bfloat16)
    logw = -jnp.asarray(rs.uniform(0.1, 2.0, (BH, T, D)), jnp.bfloat16)
    u = jnp.asarray(rs.normal(size=(D,)), jnp.bfloat16) * 0.3
    o_k, _ = wkv6(r, k, v, logw, u, chunk=16)
    o_r, _ = wkv6_ref(r.astype(jnp.float32), k.astype(jnp.float32),
                      v.astype(jnp.float32), logw.astype(jnp.float32),
                      u.astype(jnp.float32))
    assert o_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r), atol=0.15, rtol=0.1)
