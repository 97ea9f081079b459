"""GPipe pipeline parallelism via shard_map + collective_permute.

Each device along the "stage" mesh axis holds one contiguous slice of
layers. Microbatches stream through: at tick t, stage s computes
microbatch (t - s) and hands its activation to stage s+1 with a
collective_permute (differentiable — its transpose is the reverse
permute, so jax.grad gives the 1F1B-equivalent backward schedule for
free; remat inside the stage keeps the bubble's live set small).

Schedule (classic GPipe): M microbatches, S stages, M + S - 1 ticks,
bubble fraction (S-1)/(M+S-1).

This is the depth-scaling option for 1000+ node deployments where the
(data, model) in-pod mesh is exhausted: stages map onto the "pod" axis so
the only cross-pod traffic is one (microbatch, d_model) activation per
tick (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,          # per-device slice (leading stage dim consumed)
    x: jax.Array,               # (M, mb, ...) microbatched input
    *,
    axis: str = "stage",
) -> jax.Array:
    """Run the GPipe schedule inside shard_map (one stage per device).

    stage_fn(params, x_mb) -> y_mb applies THIS device's layers.
    x carries all M microbatches; stage 0 feeds them in order. Returns the
    final-stage outputs in microbatch order (replicated layout handled by
    the caller's out_specs).
    """
    s_idx = jax.lax.axis_index(axis)
    n_stages = jax.lax.psum(1, axis)
    m = x.shape[0]
    mb_shape = x.shape[1:]
    n_ticks = m + n_stages - 1

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def tick(carry, t):
        buf, outs = carry                       # buf: activation entering us
        # stage 0 ingests microbatch t (others use the permuted buffer)
        x_in = jnp.where(
            s_idx == 0,
            jax.lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, m - 1), 0, keepdims=False
            ),
            buf,
        )
        y = jax.checkpoint(stage_fn)(stage_params, x_in)
        # last stage records microbatch (t - S + 1) when it is valid
        out_slot = t - (n_stages - 1)
        is_last = jnp.logical_and(s_idx == n_stages - 1, out_slot >= 0)
        outs = jnp.where(
            is_last,
            jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(out_slot, 0, m - 1), 0
            ),
            outs,
        )
        # hand activations downstream
        buf = jax.lax.ppermute(y, axis, perm)
        return (buf, outs), None

    buf0 = jnp.zeros(mb_shape, x.dtype)
    outs0 = jnp.zeros((m,) + mb_shape, x.dtype)
    (buf, outs), _ = jax.lax.scan(
        tick, (buf0, outs0), jnp.arange(n_ticks)
    )
    # only the last stage holds real outputs; broadcast them to all stages
    # (psum of a masked buffer == select from last stage)
    mask = (s_idx == n_stages - 1).astype(outs.dtype)
    return jax.lax.psum(outs * mask, axis)


def build_gpipe_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
    *,
    axis: str = "stage",
    params_spec: Any,
):
    """shard_map-wrapped GPipe apply: (stacked stage params, (M, mb, ...) x)
    -> (M, mb, ...) y, with per-stage params sharded along ``axis``."""

    def apply(stacked_params, x):
        local = jax.tree.map(lambda v: v[0], stacked_params)  # our stage slice
        return pipeline_forward(stage_fn, local, x, axis=axis)

    return jax.shard_map(
        apply,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
        check_vma=False,
    )


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
