"""Stream compaction in jnp (prefix-sum + scatter), the one implementation.

Contract: given ``mask (N,)`` and row payloads
``vals (N, C)``, pack the rows where ``mask`` is True — in ascending input
order — into the first ``count = min(sum(mask), n_out)`` rows of an
``(n_out, C)`` buffer.  Rows past ``count`` are unspecified (callers gate on
the returned count); overflowing elements (output position >= n_out) are the
highest-index survivors and are dropped, matching the legacy host engine's
``max_frontier`` clamp.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def compact_ref(mask: jax.Array, vals: jax.Array, n_out: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Reference compaction: (count () int32, packed (n_out, C))."""
    mask = mask.astype(bool)
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1          # inclusive scan - 1
    tgt = jnp.where(mask, pos, n_out)                     # parked at n_out
    out = jnp.zeros((n_out,) + vals.shape[1:], vals.dtype)
    out = out.at[tgt].set(vals, mode="drop")              # scatter; OOB drops
    count = jnp.minimum(jnp.sum(mask.astype(jnp.int32)), n_out)
    return count, out
