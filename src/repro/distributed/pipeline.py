"""GPipe-style pipeline parallelism over a mesh axis via ppermute.

Each device along ``axis`` owns one STAGE (a slice of layer repeats);
microbatch activations circulate stage-to-stage with
``lax.ppermute`` inside a shard_map, using the classic rotating-buffer
schedule: step t runs stage s on microbatch (t - s); the pipeline
drains after n_micro + n_stages - 1 steps.  Bubble fraction =
(n_stages - 1) / (n_micro + n_stages - 1).

This is the composable runtime primitive (correctness-tested on an
8-device debug mesh in tests/test_pipeline.py); the 40 dry-run cells
use the pod axis for data parallelism by default (DESIGN.md §5), with
PP available for depth-dominated models via this module.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(stage_fn: Callable, stage_params, xs: jax.Array, *, mesh: Mesh,
          axis: str):
    """Run a pipelined stack.

    stage_fn(params_one_stage, h) -> h     (same shape in/out)
    stage_params: pytree with a leading stage dim == mesh.shape[axis]
    xs: (n_micro, mb, ...) microbatched inputs (replicated).
    Returns (n_micro, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = xs.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(params_loc, xs_loc):
        params_one = jax.tree_util.tree_map(lambda a: a[0], params_loc)
        sid = jax.lax.axis_index(axis)

        def body(h, t):
            inject = xs_loc[jnp.minimum(t, n_micro - 1)]
            h = jnp.where((sid == 0) & (t < n_micro), inject, h)
            y = stage_fn(params_one, h)
            out = jnp.where(sid == n_stages - 1, y, jnp.zeros_like(y))
            h_next = jax.lax.ppermute(y, axis, perm)
            return h_next, out

        _, outs = jax.lax.scan(body, jnp.zeros_like(xs_loc[0]),
                               jnp.arange(steps))
        # Only the last stage produced nonzero outputs; psum replicates
        # them to every stage.  Valid rows are the last n_micro steps.
        outs = jax.lax.psum(outs[n_stages - 1:], axis)
        return outs

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(*([None] * xs.ndim))),
        out_specs=P(*([None] * xs.ndim)),
        check_vma=False)
    return fn(stage_params, xs)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
