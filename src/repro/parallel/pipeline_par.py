"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

Layers are split into ``n_stages`` contiguous groups; microbatches flow
through stages via ``jax.lax.ppermute`` inside shard_map.  The schedule is
the classic GPipe loop with (n_micro + n_stages - 1) ticks; each tick every
stage processes one resident microbatch and then the ring rotates
activations forward.  Intended for the `pod` axis on the multi-pod mesh
(cross-DCN traffic = one activation tensor per tick), as an alternative to
pure FSDP over pods.  Forward-only demonstration + tests; the training path
in this repo uses FSDP/TP which covers the assigned cells.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _pcast(x, axis_names, to="varying"):
    """jax.lax.pcast when available (varying-type marking for the new
    shard_map); identity on older jax, whose shard_map has no varying
    check."""
    pcast = getattr(jax.lax, "pcast", None)
    if pcast is None:
        return x
    return pcast(x, axis_names, to=to)


def pipeline_forward(stage_fn: Callable, n_stages: int, n_micro: int,
                     axis_name: str):
    """Build a shard_map-able pipelined forward.

    stage_fn(stage_params, x) -> x, applied by each stage to its resident
    microbatch.  Inputs inside shard_map: stage_params (this stage's layer
    stack), microbatches (n_micro, mb, ...) resident on stage 0.
    """

    def fn(stage_params, micro):
        stage = jax.lax.axis_index(axis_name)
        mb_shape = micro.shape[1:]
        n_ticks = n_micro + n_stages - 1
        # `current` holds the activation resident on this stage this tick.
        # pcast marks the carries as varying over the stage axis (their
        # values genuinely differ per stage once the ring rotates).
        current = _pcast(jnp.zeros(mb_shape, micro.dtype),
                         (axis_name,), to="varying")
        outputs = _pcast(
            jnp.zeros((n_micro,) + mb_shape, micro.dtype),
            (axis_name,), to="varying")

        def tick(t, carry):
            current, outputs = carry
            # Stage 0 injects microbatch t (if any remain).
            inject = jax.lax.dynamic_index_in_dim(
                micro, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            current = jnp.where((stage == 0) & (t < n_micro), inject,
                                current)
            # Every stage applies its layers to its resident activation.
            current = stage_fn(stage_params, current)
            # Last stage emits output for microbatch (t - n_stages + 1).
            # Predicated update (a lax.cond here trips shard_map's varying-
            # type check across branches).
            out_idx = t - (n_stages - 1)
            emit = (stage == n_stages - 1) & (out_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, current, jnp.maximum(out_idx, 0), 0)
            outputs = jnp.where(emit, updated, outputs)
            # Rotate the ring: stage i -> stage i+1.
            current = jax.lax.ppermute(
                current, axis_name,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return current, outputs

        _, outputs = jax.lax.fori_loop(0, n_ticks, tick,
                                       (current, outputs))
        # Outputs live on stage n-1; broadcast so every stage returns them.
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis_name)
        return outputs

    return fn


def run_pipelined(mesh: Mesh, axis_name: str, stage_fn: Callable,
                  stacked_params, micro: jax.Array, n_stages: int):
    """Convenience wrapper: shard params/layers over the stage axis and run.

    stacked_params leaves have leading dim n_stages (one slice per stage).
    micro: (n_micro, mb, ...) global.
    """
    n_micro = micro.shape[0]
    fn = pipeline_forward(stage_fn, n_stages, n_micro, axis_name)
    pspec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    sm = shard_map(
        lambda p, m: fn(jax.tree.map(lambda a: a[0], p), m),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
    )
    return sm(stacked_params, micro)
