"""GPipe-style pipeline parallelism over a ``pipe`` mesh axis.

shard_map + lax.ppermute: layer groups are split into S stages (stage s
holds its own slice of the stacked layer params); microbatches stream
through the classic GPipe schedule — at step t, stage s computes microbatch
(t - s). Differentiation works through the schedule automatically: the
transpose of ppermute is the reverse permute, so jax.grad of the pipelined
forward *is* the GPipe backward (bubble included).

This is the 1000+-node scaling dimension the 2D (data x model) mesh lacks:
at fixed global batch, pipe stages multiply the reachable chip count
without widening TP. ``make_pipeline_mesh()`` (4 x 8 x 16 = 512) +
tests/test_pipeline.py prove the lowering; examples stay 2D because every
assigned arch fits the 2D mesh (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _shift_right(x: Array, axis: str) -> Array:
    n = jax.lax.axis_size(axis)
    return jax.lax.ppermute(x, axis, [(i, (i + 1) % n) for i in range(n)])


def pipeline_apply(stage_fn: Callable[[Any, Array], Array], n_stages: int,
                   n_microbatches: int, mesh: Mesh, *, axis: str = "pipe",
                   extra_specs: P = P()) -> Callable[[Any, Array], Array]:
    """Build a pipelined forward.

    stage_fn(stage_params, x_mb) -> x_mb : one stage's computation on one
      microbatch (a slice of the layer stack, scanned internally).
    params: pytree with leading dim n_stages on every leaf (stage-stacked).
    x: (n_microbatches, mb, ...) microbatched input.
    Returns (n_microbatches, mb, ...) outputs (as produced by the last
    stage, gathered back to all pipe shards for the loss).
    """
    steps = n_stages + n_microbatches - 1

    def pipelined(params: Any, x: Array) -> Array:
        s_idx = jax.lax.axis_index(axis)
        mb_shape = x.shape[1:]

        def body(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t; everyone else takes the
            # neighbour's activation from the previous step
            inject = x[jnp.minimum(t, n_microbatches - 1)]
            state = jnp.where(s_idx == 0, inject, state)
            state = stage_fn(params, state)
            # last stage's finished microbatch lands in the output buffer
            out_t = t - (n_stages - 1)
            write = (s_idx == n_stages - 1) & (out_t >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(write, state, jax.lax.dynamic_index_in_dim(
                    outputs, jnp.maximum(out_t, 0), keepdims=False)),
                jnp.maximum(out_t, 0), axis=0)
            # hand activations to the next stage
            state = _shift_right(state, axis)
            return (state, outputs), None

        init = (jnp.zeros(mb_shape, x.dtype),
                jnp.zeros((n_microbatches,) + mb_shape, x.dtype))
        (_, outputs), _ = jax.lax.scan(body, init, jnp.arange(steps))
        # outputs are populated only on the last stage: broadcast them to
        # every pipe shard so the (replicated-over-pipe) loss sees them
        outputs = jax.lax.psum(
            jnp.where(s_idx == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    def run(params: Any, x: Array) -> Array:
        return shard_map(pipelined, mesh=mesh,
                         in_specs=(P(axis), P()), out_specs=P(),
                         check_vma=False)(params, x)

    return run


def stack_stages(params_layers: Any, n_stages: int) -> Any:
    """Reshape leading layer dim L -> (n_stages, L/n_stages) on every leaf."""

    def re(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape((n_stages, l // n_stages) + p.shape[1:])

    return jax.tree.map(re, params_layers)
