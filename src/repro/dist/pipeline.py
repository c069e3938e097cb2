"""Microbatch pipeline parallelism via shard_map + ppermute (DESIGN.md §4).

GPipe-style schedule on a ring: each mesh rank along `axis_name` owns one
stage's parameters; activations flow rank -> rank+1 one hop per tick. With
S stages and M microbatches the loop runs S + M - 1 ticks; rank r is busy on
ticks [r, r + M), so bubble overhead is (S-1)/(S+M-1).

Only the stage handoff (one microbatch of activations) crosses the link per
tick — weights never move. The returned function is jit-safe and closes over
the mesh, so it is called as ``jax.jit(pipe)(stage_params, x)`` with
full (unsharded) inputs; shard_map splits the stage dim internally.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

__all__ = ["build_pipeline_fn"]


def build_pipeline_fn(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                      n_stages: int, n_micro: int, mesh,
                      axis_name: str) -> Callable:
    """Build ``pipe(stage_params, x) -> y``.

    stage_fn:     (per-stage params, microbatch activations) -> activations
                  (shape-preserving on the activations).
    stage_params: pytree whose leaves have a leading n_stages dim (sharded
                  one stage per rank).
    x:            (n_micro, *microbatch_shape) — replicated input; y has the
                  same shape and equals sequentially applying every stage.
    """
    if mesh.shape.get(axis_name) != n_stages:
        raise ValueError(
            f"pipeline needs mesh axis {axis_name!r} == n_stages "
            f"({mesh.shape.get(axis_name)} != {n_stages})")
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    ticks = n_stages + n_micro - 1

    def body(stage_loc, x_full):
        # stage_loc leaves: (1, ...) — this rank's stage
        W = jax.tree_util.tree_map(lambda w: w[0], stage_loc)
        r = jax.lax.axis_index(axis_name)
        h0 = jnp.zeros(x_full.shape[1:], x_full.dtype)
        out0 = jnp.zeros_like(x_full)

        def tick(t, carry):
            h, out = carry
            # stage 0 feeds from the input stream; later stages from the ring
            mb = jnp.clip(t, 0, n_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(x_full, mb, 0, keepdims=False)
            y = stage_fn(W, jnp.where(r == 0, x_in, h))
            # the last stage emits microbatch t - (S-1) once the fill ends
            oi = t - (n_stages - 1)
            emit = jnp.logical_and(r == n_stages - 1, oi >= 0)
            written = jax.lax.dynamic_update_index_in_dim(
                out, y, jnp.clip(oi, 0, n_micro - 1), 0)
            out = jnp.where(emit, written, out)
            h = jax.lax.ppermute(y, axis_name, perm=fwd)
            return h, out

        _, out = jax.lax.fori_loop(0, ticks, tick, (h0, out0))
        # only the last rank wrote anything; psum broadcasts the result
        return jax.lax.psum(out, axis_name)

    return shard_map(body, mesh=mesh, in_specs=(P(axis_name), P()),
                     out_specs=P(), check_vma=False)
