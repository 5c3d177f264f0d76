"""Distributed KMeans in pure JAX.

This is the substrate for (a) IVF / HI² cluster-selector training
(paper §4.1: cluster embeddings initialized by KMeans over all document
embeddings) and (b) PQ sub-codebook training (paper §3.2, one KMeans per
embedding fragment).

TPU adaptation: assignment is a blocked matmul (``x @ c.T`` on the MXU,
argmax over clusters) instead of Faiss's CPU heap scan; centroid updates
are ``segment_sum`` scatters. The distributed variant shards points over
the mesh's data axes and completes the update with ``psum`` — the only
cross-device traffic is the (L, h) partial-sum planes, never the points.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array


def _pad_to_multiple(x: Array, block: int, axis: int = 0,
                     value=0.0) -> tuple[Array, int]:
    n = x.shape[axis]
    rem = (-n) % block
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=value), n


def map_blocks(fn, xs, block: int):
    """``fn`` over ``block``-row slices of the arrays ``xs`` (a pytree
    of arrays with the same leading dim n), one slice at a time: the
    last slice is zero-padded and the padding stripped from the
    results, so only (block, ...) temporaries ever exist."""
    n = jax.tree.leaves(xs)[0].shape[0]
    blocked = jax.tree.map(
        lambda a: _pad_to_multiple(a, block)[0].reshape(-1, block,
                                                        *a.shape[1:]), xs)
    return jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:])[:n],
                        jax.lax.map(fn, blocked))


def assign_blocked(x: Array, centroids: Array, block: int = 4096) -> Array:
    """argmin_j ||x_i - c_j||² for every point, computed in MXU-friendly blocks.

    ||x - c||² = ||x||² - 2<x,c> + ||c||²; the ||x||² term is constant per
    point so the argmin reduces to argmax(<x,c> - ||c||²/2).
    """
    c_norm = 0.5 * jnp.sum(centroids.astype(jnp.float32) ** 2, axis=-1)  # (L,)

    def one_block(xi):
        scores = xi.astype(jnp.float32) @ centroids.T.astype(jnp.float32) - c_norm
        return jnp.argmax(scores, axis=-1).astype(jnp.int32)

    return map_blocks(one_block, x, block)


def _update(x: Array, assign: Array, n_clusters: int) -> tuple[Array, Array]:
    """Per-shard partial centroid sums + counts."""
    sums = jax.ops.segment_sum(x.astype(jnp.float32), assign, num_segments=n_clusters)
    counts = jax.ops.segment_sum(jnp.ones_like(assign, jnp.float32), assign,
                                 num_segments=n_clusters)
    return sums, counts


def _reseed_empty(key: Array, centroids: Array, counts: Array, x: Array) -> Array:
    """Empty clusters are re-seeded to random points (standard Lloyd fix).

    Fixed-shape: we draw one candidate point per cluster and use it only
    where the cluster is empty.
    """
    idx = jax.random.randint(key, (centroids.shape[0],), 0, x.shape[0])
    cand = x[idx].astype(jnp.float32)
    empty = (counts < 0.5)[:, None]
    return jnp.where(empty, cand, centroids)


@functools.partial(jax.jit, static_argnames=("n_clusters", "n_iters", "block"))
def kmeans_fit(key: Array, x: Array, n_clusters: int, n_iters: int = 20,
               block: int = 4096) -> tuple[Array, Array]:
    """Lloyd's algorithm. Returns (centroids (L,h) f32, assignments (n,) i32)."""
    n = x.shape[0]
    key, sub = jax.random.split(key)
    init_idx = jax.random.choice(sub, n, (n_clusters,), replace=n < n_clusters)
    init = x[init_idx].astype(jnp.float32)

    def body(carry, k):
        centroids = carry
        a = assign_blocked(x, centroids, block=block)
        sums, counts = _update(x, a, n_clusters)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        new = _reseed_empty(k, new, counts, x)
        return new, None

    keys = jax.random.split(key, n_iters)
    centroids, _ = jax.lax.scan(body, init, keys)
    return centroids, assign_blocked(x, centroids, block=block)


def kmeans_fit_sharded(key: Array, x_local: Array, n_clusters: int,
                       n_iters: int = 20, axis_names: tuple[str, ...] = ("data",),
                       block: int = 4096) -> Array:
    """SPMD KMeans body — call inside ``shard_map`` with points sharded over
    ``axis_names``. Centroids are replicated; each step does a local
    assign + partial update and a psum of the (L,h)+(L,) planes.
    """
    n_local = x_local.shape[0]
    key = jax.random.fold_in(key, 0)
    init_idx = jax.random.randint(key, (n_clusters,), 0, n_local)
    # every shard proposes local points; pmean so all shards agree on init
    init = jax.lax.pmean(x_local[init_idx].astype(jnp.float32), axis_names)

    def body(centroids, k):
        a = assign_blocked(x_local, centroids, block=block)
        sums, counts = _update(x_local, a, n_clusters)
        sums = jax.lax.psum(sums, axis_names)
        counts = jax.lax.psum(counts, axis_names)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        new = _reseed_empty(k, new, counts, x_local)
        new = jax.lax.pmean(new, axis_names)  # keep shards identical after reseed
        return new, None

    keys = jax.random.split(jax.random.fold_in(key, 1), n_iters)
    centroids, _ = jax.lax.scan(body, init, keys)
    return centroids


def kmeans_cost(x: Array, centroids: Array, assign: Array) -> Array:
    """Mean squared distance of points to their assigned centroid."""
    d = x.astype(jnp.float32) - centroids[assign]
    return jnp.mean(jnp.sum(d * d, axis=-1))
