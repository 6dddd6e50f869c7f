"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For deployments beyond one pod, the ``pod`` axis can run as a pipeline
axis instead of outer-DP: each pod holds a contiguous span of layer
cycles, microbatches stream through stages with ``jax.lax.ppermute``
boundary transfers, and the bubble fraction is (S-1)/(M+S-1) for S stages
and M microbatches.

This module implements the schedule generically over a user-supplied
``stage_fn(stage_params, x) -> x`` so it composes with the model zoo's
stacked-cycle parameters: stage s owns cycles [s·C/S, (s+1)·C/S).

The rotating-buffer formulation below runs every stage every tick on its
current microbatch (SPMD-friendly: no per-stage control flow), which is
the standard JAX pipelining pattern.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, stage_params, x_microbatches,
                     *, mesh: Mesh, axis: str = "pipe"):
    """Run M microbatches through S pipeline stages.

    stage_params: pytree whose leaves lead with the stage axis (sharded
      over ``axis``);
    x_microbatches: (M, mb, ...) activations (replicated across ``axis``).
    Returns (M, mb, ...) outputs from the LAST stage.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]

    def stage_local(params, xs):
        # params: leaves (1, ...) — this stage's slice; xs: (M, mb, d)
        params = jax.tree.map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        total = m + n_stages - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (or zeros when drained)
            inject = jnp.where(t < m, t, 0)
            x0 = xs[inject]
            x_in = jnp.where(idx == 0, x0, buf)
            y = stage_fn(params, x_in)
            # pass to next stage
            nxt = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            # last stage emits microbatch t - (S-1)
            emit_t = t - (n_stages - 1)
            outs = jax.lax.cond(
                emit_t >= 0,
                lambda o: o.at[jnp.maximum(emit_t, 0)].set(y),
                lambda o: o, outs)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(total))
        # only the last stage's outs are real; broadcast them back
        gathered = jax.lax.all_gather(outs, axis)      # (S, M, mb, d)
        return gathered[n_stages - 1]

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(stage_local, mesh=mesh,
                       in_specs=(spec_params, P()), out_specs=P(),
                       check_vma=False)
    return fn(stage_params, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
