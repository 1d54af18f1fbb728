"""Batched ball query (``ball_query``): the first ``k`` points by index
within a radius of each centre, for every centre of B problems in one
Pallas call.

Semantics are those of ``pointnet2_ops``' ``ball_query``: a point is a
neighbour when ``d2 < r*r`` (float32, ``d = centre - point``, ``d2 =
(dx*dx + dy*dy) + dz*dz``); the first ``k`` neighbours by point index fill
the slots in order, and slots past the last neighbour repeat the first
one (all 0 when a ball is empty).  The kernel also returns how many slots
hold a neighbour of their own (the ball's fill).

One grid step holds one problem's points in VMEM as a ``(3, N)`` plane
(points on lanes) and a block of ``R`` centres (one a sublane row), and
works in two phases:

1. *Hits as bits.*  Each 128-point chunk ``c`` of the cloud is tested
   against all ``R`` centres at once, and its hits are set as bit
   ``c % 31`` of word ``c // 31`` of the point's lane: after one pass,
   lane ``l`` of a row holds in a few int32 words which chunks have a
   neighbour at lane ``l``.
2. *First hits, one slot a step.*  The next neighbour of a row in index
   order is the lane whose lowest set bit is lowest (ties to the lower
   lane), so slot ``j`` of every row is one lowest-bit extraction (``w &
   -w``, its exponent read from its float32 form), one lane minimum and
   one bit cleared.  The loop runs as many steps as the fullest row of
   the block has slots to fill, never more than ``k``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Chunks whose hits one int32 word holds (its sign bit stays clear).
BITS = 31


def ball_query_kernel(c_ref, p_ref, idx_ref, cnt_ref, fill_ref, *, n: int,
                      k: int, r2: float):
    """``c_ref`` (1, R, 3) centres, ``p_ref`` (1, 3, Np) points; out:
    ``idx_ref`` (1, R, k) int32 and ``cnt_ref`` (1, R, 1) int32 filled
    slots; ``fill_ref`` (1, 1) int32 SMEM scratch."""
    rows = c_ref.shape[1]
    chunks = p_ref.shape[2] // LANES
    shape = (rows, LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    cx, cy, cz = (c_ref[0, :, a:a + 1] for a in range(3))

    # -- phase 1: every chunk's hits, as one bit a chunk ------------------
    words = []
    count = jnp.zeros(shape, jnp.int32)
    for q in range(-(-chunks // BITS)):
        def chunk(b, carry, q=q):
            word, count = carry
            c = q * BITS + b
            start = pl.multiple_of(c * LANES, LANES)
            px, py, pz = (p_ref[0, a:a + 1, pl.ds(start, LANES)]
                          for a in range(3))
            dx, dy, dz = cx - px, cy - py, cz - pz
            d2 = (dx * dx + dy * dy) + dz * dz
            hit = (d2 < r2) & (lane + c * LANES < n)
            word = word | jnp.where(hit, jnp.left_shift(jnp.int32(1), b), 0)
            return word, count + hit.astype(jnp.int32)

        word, count = jax.lax.fori_loop(
            0, min(BITS, chunks - q * BITS), chunk,
            (jnp.zeros(shape, jnp.int32), count))
        words.append(word)

    filled = jnp.minimum(jnp.sum(count, axis=1, keepdims=True), k)
    cnt_ref[0] = filled
    fill_ref[0, 0] = jnp.max(filled)

    # -- phase 2: slot j of every row takes the row's next hit ------------
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    none = jnp.int32(1 << 30)

    def take(j, carry):
        out, words = carry
        key = jnp.full(shape, none)
        for q in reversed(range(len(words))):
            w = words[q]
            low = (w & (-w)).astype(jnp.float32)
            bit = (pltpu.bitcast(low, jnp.int32) >> 23) - 127
            key = jnp.where(w != 0, (q * BITS + bit) * LANES + lane, key)
        first = jnp.min(key, axis=1, keepdims=True)       # the point index
        out = jnp.where(slot == j, first, out)
        at = lane == (first & (LANES - 1))
        c = first >> 7                                    # // LANES
        words = tuple(
            jnp.where(at & (c >= q * BITS) & (c < (q + 1) * BITS),
                      w & (w - 1), w)
            for q, w in enumerate(words))
        return out, words

    out, _ = jax.lax.fori_loop(0, fill_ref[0, 0], take,
                               (jnp.zeros((rows, k), jnp.int32),
                                tuple(words)))
    pad = jnp.where(filled > 0, out[:, 0:1], 0)
    idx_ref[0] = jnp.where(slot < filled, out, pad)


def make_ball_query_call(b: int, m_pad: int, n_pad: int, rows: int, n: int,
                         k: int, radius: float, interpret: bool):
    """``(b, m_pad, 3)`` centres and ``(b, 3, n_pad)`` points ->
    ``(b, m_pad, k)`` int32 indices and ``(b, m_pad, 1)`` int32 fills."""
    r = np.float32(radius)
    kernel = functools.partial(ball_query_kernel, n=n, k=k, r2=float(r * r))
    return pl.pallas_call(
        kernel,
        grid=(b, m_pad // rows),
        in_specs=[pl.BlockSpec((1, rows, 3), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((1, 3, n_pad), lambda i, j: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, rows, k), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, rows, 1), lambda i, j: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, m_pad, k), jnp.int32),
                   jax.ShapeDtypeStruct((b, m_pad, 1), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32)],
        interpret=interpret,
        name="ball_query",
    )
