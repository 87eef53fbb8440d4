"""Pipeline parallelism (GPipe schedule) over a "stage" mesh axis.

The production dry-run meshes use (pod, data, model); for deployments that
prefer pipeline over wider TP (e.g. cross-pod pipelining to hide DCI
latency), this module runs a stage-partitioned stack under shard_map with
``collective_permute`` boundary transfers and the standard GPipe
microbatch schedule:

    for t in range(num_micro + stages - 1):        # fill + steady + drain
        x = stage_fn(stage_params, x)  if active
        x = ppermute(x, stage -> stage+1)

Each device holds ``layers/stages`` contiguous layers; bubble fraction is
``(stages-1)/(num_micro+stages-1)``.  Forward-only is exposed for serving;
training composes with jax.grad through shard_map (linear collectives
differentiate), validated in tests against the unpipelined stack.
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["gpipe_forward"]


def gpipe_forward(mesh: Mesh, stage_axis: str, stage_fn: Callable,
                  stage_params, x: jax.Array, num_micro: int) -> jax.Array:
    """Run ``stage_fn`` as a GPipe pipeline.

    Args:
      stage_fn: (params_slice, x_micro) -> x_micro, one stage's layers.
      stage_params: pytree whose leaves have a leading ``stages`` dim,
        sharded over ``stage_axis``.
      x: (B, ...) global input batch, replicated across stages.
      num_micro: number of microbatches (must divide B).

    Returns (B, ...) outputs (valid on the last stage; replicated out).
    """
    stages = mesh.shape[stage_axis]
    b = x.shape[0]
    if b % num_micro:
        raise ValueError(f"batch {b} must divide into {num_micro} micro")
    mb = b // num_micro
    perm_fwd = [(i, (i + 1) % stages) for i in range(stages)]

    def body(params_l, x_l):
        # params_l leaves: (1, ...) — this stage's slice
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_l)
        stage_id = jax.lax.axis_index(stage_axis)
        micro = x_l.reshape(num_micro, mb, *x_l.shape[1:])

        n_ticks = num_micro + stages - 1
        buf = jnp.zeros((mb, *x_l.shape[1:]), x_l.dtype)
        outs = jnp.zeros_like(micro)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = micro[jnp.clip(t, 0, num_micro - 1)]
            cur = jnp.where(stage_id == 0, feed, buf)
            active = (t - stage_id >= 0) & (t - stage_id < num_micro)
            y = stage_fn(params_local, cur)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            out_idx = jnp.clip(t - stages + 1, 0, num_micro - 1)
            record = active & (stage_id == stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record, y, outs[out_idx]), out_idx, 0)
            # shift activations one stage forward
            buf = jax.lax.ppermute(y, stage_axis, perm_fwd)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # broadcast the last stage's finished outputs to every stage
        outs = jax.lax.all_gather(outs, stage_axis)[stages - 1]
        return outs.reshape(b, *x_l.shape[1:])

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x)
