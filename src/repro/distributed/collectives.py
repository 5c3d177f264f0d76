"""Collective helpers for the (pod, data, model) production mesh.

The interesting one is the **hierarchical gradient all-reduce**: at 512+
chips a flat all-reduce over (pod × data) serializes on the slow
cross-pod (DCI) links.  The bandwidth-optimal schedule is

    reduce_scatter(data)  →  all_reduce(pod)  →  all_gather(data)

which moves 1/|data| of the gradient bytes across pods.  These helpers
are `shard_map`-body functions; `launch/train.py` applies them when the
mesh has a pod axis, and `tests/test_distributed.py` proves numerical
equality with the flat psum on the 8-device host mesh.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


PyTree = Any


def flat_allreduce(grads: PyTree, axis_names: tuple[str, ...]) -> PyTree:
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_names), grads)


def hierarchical_allreduce(grads: PyTree, data_axis: str = "data",
                           pod_axis: str = "pod") -> PyTree:
    """reduce_scatter(data) → psum(pod) → all_gather(data), leafwise.

    Falls back to a flat psum for leaves too small to scatter.
    """
    data_size = jax.lax.axis_size(data_axis)

    def one(g):
        if g.ndim == 0 or g.shape[0] % data_size != 0:
            return jax.lax.psum(g, (data_axis, pod_axis))
        scattered = jax.lax.psum_scatter(g, data_axis,
                                         scatter_dimension=0, tiled=True)
        scattered = jax.lax.psum(scattered, pod_axis)
        return jax.lax.all_gather(scattered, data_axis, axis=0, tiled=True)

    return jax.tree.map(one, grads)


def pmean_metrics(metrics: PyTree, axis_names: tuple[str, ...]) -> PyTree:
    return jax.tree.map(lambda m: jax.lax.pmean(m, axis_names), metrics)


def gather_topk(scores: jax.Array, ids: jax.Array, axis_name: str
                ) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard top-R planes for the sharded HI² search
    (DESIGN.md §6): all-gather each shard's (B, R) scores/ids along the
    shard axis and lay them out as one (B, S·R) candidate plane per
    query, ready for a final total-order top-R.

    Communication is 2·S·B·R values (f32 + i32) — independent of corpus
    size and list capacities, which is the point: only the tiny merged
    frontier crosses the interconnect, never candidates or codes.  Runs
    inside a ``shard_map`` body; every shard returns the identical
    merged plane (the caller's final top-R is replicated work).
    """
    s = jax.lax.all_gather(scores, axis_name)            # (S, B, R)
    i = jax.lax.all_gather(ids, axis_name)
    n_shards, b, r = s.shape
    return (jnp.moveaxis(s, 0, 1).reshape(b, n_shards * r),
            jnp.moveaxis(i, 0, 1).reshape(b, n_shards * r))
