"""Stream compaction for the per-level wavefront engines.

``stream_compact`` is the one entry point the wavefront engine calls each
octree level: an XLA prefix sum plus scatter (:func:`compact_ref`), the
same program on every backend.  A Pallas scatter kernel is not used: the
TPU compiler has no in-kernel ``cumsum``, and a VMEM-resident output
window does not scale to the frontier capacities of paper-size batches.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.compact.ref import compact_ref


def stream_compact(mask: jax.Array, vals: jax.Array, n_out: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """Pack rows of ``vals`` where ``mask`` holds into an (n_out, C) buffer.

    Returns (count () int32, packed (n_out, C)).  Rows past ``count`` are
    unspecified; survivors that would land past ``n_out`` are dropped.
    """
    return compact_ref(mask, vals.astype(jnp.int32), n_out)


def compact_pairs(mask: jax.Array, q_idx: jax.Array, codes: jax.Array,
                  n_out: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Frontier-specific wrapper: compact (query, Morton code) int32/uint32
    pairs in one pass.  Returns (count, q_idx (n_out,), codes (n_out,))."""
    vals = jnp.stack(
        [q_idx.astype(jnp.int32),
         jax.lax.bitcast_convert_type(codes, jnp.int32)], axis=-1)
    count, packed = stream_compact(mask, vals, n_out)
    return (count, packed[:, 0],
            jax.lax.bitcast_convert_type(packed[:, 1], jnp.uint32))
