"""GPipe-style pipeline parallelism over a 'pp' mesh axis.

Each pipeline rank holds ONE stage's parameters (the stacked stage dim is
sharded over 'pp'). Microbatches stream through the classic skewed
schedule: at tick t, rank s processes microbatch (t - s); activations hop
rank-to-rank with ``ppermute`` (ICI-neighbor traffic only). Bubble
fraction is the standard (S-1)/(T+S-1).

This is the optional PP feature (the production dry-run mesh uses
DP x TP(+EP/SP), which fits every assigned arch); it composes with the
other axes by nesting the 'pp' axis into the mesh, e.g.
``jax.make_mesh((4, 8, 8), ("pp", "data", "model"))``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stacked_params, x_micro, mesh,
                   pp_axis: str = "pp"):
    """Run ``n_micro`` microbatches through S pipeline stages.

    stage_fn(params_for_one_stage, x) -> y, with y.shape == x.shape
    stacked_params: pytree with leading dim S (sharded over pp_axis)
    x_micro: (n_micro, mb, ...) microbatched input (replicated)

    Returns (n_micro, mb, ...) outputs (replicated across pp ranks).
    """
    n_stages = mesh.shape[pp_axis]
    n_micro = x_micro.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_stk, xs):
        s = jax.lax.axis_index(pp_axis)
        params = jax.tree.map(lambda a: a[0], params_stk)  # local stage
        act = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            act, outs = carry
            mb_idx = t - s
            active = jnp.logical_and(mb_idx >= 0, mb_idx < n_micro)
            # stage 0 injects a fresh microbatch; others use the arrival
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            x_in = jnp.where(s == 0, inject, act)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, x_in)
            # last stage banks its finished microbatch
            outs = jax.lax.cond(
                jnp.logical_and(active, s == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # hop rightward for the next tick
            act = jax.lax.ppermute(y, pp_axis, perm)
            return act, outs

        act, outs = jax.lax.fori_loop(
            0, n_micro + n_stages - 1, tick, (act, outs))
        # broadcast the last rank's bank to every rank
        is_last = (s == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * is_last, pp_axis)

    param_specs = jax.tree.map(lambda _: P(pp_axis), stacked_params)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
