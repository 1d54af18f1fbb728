"""JIT wrapper of the batched FPS kernel: pads and lays out the points."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fps.kernel import BLOCK, LANES, make_fps_call


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def fps_sample(xyz: jax.Array, m: int, interpret: bool | None = None
               ) -> jax.Array:
    """Farthest point sampling of ``m`` picks from each of B clouds in one
    kernel call: ``xyz`` (B, N, 3) -> (B, m) int32 indices (see
    :mod:`repro.kernels.fps.kernel` for the semantics).  ``interpret``
    defaults to the interpreter off the TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, N, _ = xyz.shape
    b_pad, n_pad = _pad_to(B, BLOCK), _pad_to(N, LANES)
    m_pad = _pad_to(m, LANES)
    planes = jnp.transpose(xyz.astype(jnp.float32), (2, 0, 1))
    planes = jnp.pad(planes, ((0, 0), (0, b_pad - B), (0, n_pad - N)))
    call = make_fps_call(b_pad // BLOCK, n_pad, m_pad, N, m, interpret)
    return call(planes)[:B, :m]
