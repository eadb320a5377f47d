"""GPipe-style pipeline parallelism over the 'pod' axis.

Inter-pod links are the slow tier of a multi-pod system, which is exactly
where pipeline parallelism belongs: each pod holds a contiguous block of
layers (a stage); microbatches stream through stages with activations
handed off by ``jax.lax.ppermute`` inside ``shard_map``.

This is the selectable alternative to pure DP over 'pod' (the dry-run
default).  The schedule is 1F1B-flush (GPipe): with M microbatches and P
stages, bubble fraction = (P-1)/(M+P-1).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def pipeline_forward(stage_fn, stage_params, x_micro, *, mesh,
                     axis: str = "pod"):
    """Run microbatches through pipeline stages laid out on ``axis``.

    stage_fn: (params_slice, x) -> x        one stage's computation
    stage_params: pytree with leading dim = n_stages (sharded over axis)
    x_micro: (n_micro, mb, ...) microbatched input (replicated)
    Returns (n_micro, mb, ...) outputs (valid on the LAST stage; earlier
    stages hold zeros — caller reduces or reads from the last stage).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]

    def per_stage(params_slice, xs):
        stage = jax.lax.axis_index(axis)
        params_local = jax.tree.map(lambda a: a[0], params_slice)
        n_steps = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t; others use the handed-off act
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            live = (t - stage >= 0) & (t - stage < n_micro)
            y = stage_fn(params_local, x_in)
            y = jnp.where(live, y, jnp.zeros_like(y))
            # hand off to the next stage (ring; last stage's output wraps
            # to stage 0 where it is ignored)
            nxt = jax.lax.ppermute(
                y, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            mb_idx = jnp.clip(t - stage, 0, n_micro - 1)
            outs = jnp.where(
                (stage == n_stages - 1) & live,
                outs.at[mb_idx].set(y), outs)
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(step, (buf, outs),
                                    jnp.arange(n_steps))
        # only the last stage holds real outputs; psum replicates them
        # (all other stages contribute zeros)
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),      # stage dim sharded; input replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
