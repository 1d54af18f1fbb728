"""JIT wrapper of the batched ball-query kernel: pads and lays out."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ballquery.kernel import LANES, make_ball_query_call

#: Centres one grid step holds, at most.
MAX_ROWS = 64


@functools.partial(jax.jit, static_argnames=("radius", "k", "interpret"))
def ball_query(xyz: jax.Array, centres: jax.Array, radius: float, k: int,
               interpret: bool | None = None
               ) -> Tuple[jax.Array, jax.Array]:
    """``pointnet2`` ball query of B problems in one kernel call: ``xyz``
    (B, N, 3) points, ``centres`` (B, M, 3) -> (idx (B, M, k) int32,
    filled (B, M) int32); see :mod:`repro.kernels.ballquery.kernel`.
    ``interpret`` defaults to the interpreter off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, N, _ = xyz.shape
    M = centres.shape[1]
    rows = min(MAX_ROWS, -(-M // 8) * 8)
    m_pad = -(-M // rows) * rows
    n_pad = -(-N // LANES) * LANES
    pts = jnp.pad(jnp.transpose(xyz.astype(jnp.float32), (0, 2, 1)),
                  ((0, 0), (0, 0), (0, n_pad - N)))
    ctr = jnp.pad(centres.astype(jnp.float32), ((0, 0), (0, m_pad - M),
                                                 (0, 0)))
    call = make_ball_query_call(B, m_pad, n_pad, rows, N, k, radius,
                                interpret)
    idx, filled = call(ctr, pts)
    return idx[:, :M], filled[:, :M, 0]
