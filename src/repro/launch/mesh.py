"""Production mesh construction.

A FUNCTION (not module-level state) so importing this module never touches
jax device initialization.  Single pod: 256 chips as (16 data, 16 model);
multi-pod: 2 pods x 256 chips as (2 pod, 16 data, 16 model) with `pod` as
an extra FSDP/DP axis (DCN-ish) — the dry-run proves the `pod` axis shards.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests/examples on CPU)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"))


def use_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)
