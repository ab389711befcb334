"""Pipeline parallelism (GPipe schedule) on a "stage" mesh axis.

Completes the parallelism menu (DP/TP/EP/SP live in sharding.py; PP here).
Stages hold disjoint layer groups (params stacked on a leading stage dim,
sharded over the axis); microbatches stream through via collective-permute.
Wall-clock steps = n_micro + n_stages − 1 (the GPipe bubble); activations
cross stages once per step — ICI-neighbour traffic only, which is why PP is
the inter-pod axis of choice when DCI bandwidth is the binding constraint
(DESIGN.md §5).

This is the runtime mechanism; model integration slices a layer stack into
`n_stages` groups (`split_stages`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

PyTree = Any


def split_stages(stacked_params: PyTree, n_stages: int) -> PyTree:
    """(L, ...) layer-stacked params → (n_stages, L/n_stages, ...)."""
    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])
    return jax.tree_util.tree_map(r, stacked_params)


def pipeline_apply(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """GPipe forward.

    stage_fn(params_for_one_stage, microbatch) -> microbatch (same shape).
    stage_params: leading dim = n_stages (sharded over ``axis``).
    x: (n_micro, mb, ...) microbatched input (replicated).
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    total = n_micro + n_stages - 1

    def per_device(params, xs):
        stage = jax.lax.axis_index(axis)
        fwd_pairs = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            inp_prev, outputs = carry
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            own = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(mb_idx, 0, n_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, own, inp_prev)
            y = stage_fn(jax.tree_util.tree_map(lambda p: p[0], params), x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            record = active & (stage == n_stages - 1)
            outputs = jnp.where(
                record,
                jax.lax.dynamic_update_index_in_dim(
                    outputs, y, jnp.clip(mb_idx, 0, n_micro - 1), axis=0),
                outputs)
            # hand activations to the next stage
            y_next = jax.lax.ppermute(y, axis, fwd_pairs) \
                if n_stages > 1 else y
            return (y_next, outputs), None

        zero_in = jnp.zeros_like(xs[0])
        zero_out = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            step, (zero_in, zero_out), jnp.arange(total))
        # only the last stage holds real outputs; psum broadcasts them
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    param_spec = P(axis)
    return shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)


def pipeline_bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe efficiency model: bubble = (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
