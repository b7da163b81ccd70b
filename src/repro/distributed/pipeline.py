"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe``
mesh axis, built on shard_map + collective_permute.

The production mesh in this repo defaults to (pod, data, model) — PP is an
*optional* axis for deployments whose interconnect topology favors it
(e.g. sparse inter-pod links); `make_pp_mesh` builds (pipe, data, model).

Schedule: the classic GPipe loop with M microbatches over S stages runs
S + M − 1 ticks; each tick every stage processes one resident microbatch
and ppermutes its activation to the next stage.  Bubble fraction
(S − 1)/(S + M − 1) — reported by :func:`bubble_fraction` so configs can
size M.

The stage function is arbitrary (typically a slice of the layer stack —
``num_layers/S`` scanned blocks); stage parameters live sharded on the
pipe axis so each device holds only its stage's weights.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_stages + num_microbatches - 1)


def make_pp_mesh(num_stages: int, data: int = 1, model: int = 1) -> Mesh:
    from repro.launch.mesh import auto_mesh

    return auto_mesh((num_stages, data, model), ("pipe", "data", "model"))


def pipeline_apply(
    stage_fn: Callable,          # (stage_params, x) -> y
    stage_params,                # params with leading stage axis, sharded on pipe
    x: jax.Array,                # (num_microbatches, mb, ...) microbatched input
    mesh: Mesh,
    *,
    num_microbatches: int,
) -> jax.Array:
    """Run the GPipe schedule. Returns outputs with microbatch leading dim.

    x is sharded on the pipe axis by microbatch position per the standard
    circular-rotation formulation: each stage s processes microbatch
    (t − s) at tick t; activations rotate s -> s+1 between ticks.
    """
    num_stages = mesh.shape["pipe"]
    ticks = num_stages + num_microbatches - 1

    def per_stage(params, xs):
        # params: (1, ...) this stage's slice; xs: (num_microbatches, mb, ...)
        stage = jax.lax.axis_index("pipe")
        params = jax.tree.map(lambda p: p[0], params)
        mb_shape = xs.shape[1:]

        state = jnp.zeros(mb_shape, xs.dtype)       # resident activation
        outputs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, num_microbatches - 1)
            injected = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                    keepdims=False)
            cur = jnp.where(stage == 0,
                            jnp.where(t < num_microbatches, injected, state),
                            state)
            # every stage applies its slice to its resident microbatch
            y = stage_fn(params, cur)
            # the last stage emits: its microbatch index at tick t is
            # t − (S − 1)
            out_idx = jnp.clip(t - (num_stages - 1), 0, num_microbatches - 1)
            emit = (stage == num_stages - 1) & (t >= num_stages - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, 0),
                lambda o: o,
                outputs)
            # rotate activations stage s -> s+1
            perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
            nxt = jax.lax.ppermute(y, "pipe", perm)
            return (nxt, outputs)

        state, outputs = jax.lax.fori_loop(0, ticks, tick, (state, outputs))
        # only the last stage's outputs are real; psum_scatter-free gather:
        # zero other stages then psum over pipe
        outputs = jnp.where(stage == num_stages - 1, outputs, 0)
        outputs = jax.lax.psum(outputs, "pipe")
        return outputs

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
