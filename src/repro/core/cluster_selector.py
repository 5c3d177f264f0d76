"""Cluster selector (paper §4.1).

Associates each of the L clusters with an embedding e_C ∈ R^h:
  · documents are indexed to their argmax cluster (1 list per doc),
  · queries are dispatched to the top-K^C clusters (Eq. 6).

HI²_unsup: the embeddings come from KMeans and stay fixed.
HI²_sup:   the same tensor is a *learnable parameter* optimized by the
           distillation objective (Eq. 9/11) with the doc→cluster
           assignment φ(D) frozen after initialization (§4.3).

Scoring is a single (B, h) × (h, L) matmul + top-k — the Pallas kernel
``repro.kernels.assign_topk.ops.topk_scores`` implements the fused
version (running top-k across centroid tiles, the (B, L) score plane
never reaching HBM); the jnp path here is the oracle and the autodiff
path used in training.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import spans
from repro.core import kmeans

Array = jax.Array


class ClusterSelector(NamedTuple):
    embeddings: Array   # (L, h) f32 — learnable in HI²_sup

    @property
    def n_clusters(self) -> int:
        return self.embeddings.shape[0]


def init_kmeans(key: Array, doc_embeddings: Array, n_clusters: int,
                n_iters: int = 20) -> tuple[ClusterSelector, Array]:
    """KMeans init (both variants). Returns (selector, φ(D) assignments).

    φ(D) is the INNER-PRODUCT argmax over the KMeans centroids (paper
    §4.1: "indexed to the cluster with the highest score" ⟨e_D, e_C⟩) —
    not the L2 assignment KMeans itself used.  Host span
    ``hi2.build.kmeans`` (DESIGN.md §9).
    """
    with spans.span("hi2.build.kmeans"):
        centroids, _ = kmeans.kmeans_fit(key, doc_embeddings,
                                         n_clusters=n_clusters,
                                         n_iters=n_iters)
        selector = ClusterSelector(embeddings=centroids)
        assign = select_for_doc(selector, doc_embeddings)
        if spans.recording():       # the span ends where the work does
            jax.block_until_ready((selector, assign))
    return selector, assign


#: documents per row block of the indexing-side score plane: the
#: (n_docs, L) plane never exists — 2^20 docs × 10k clusters would be
#: 42 GB of f32 — only (DOC_BLOCK, L) tiles of it, one at a time
DOC_BLOCK = 4096


@jax.jit
def scores(selector: ClusterSelector, x: Array) -> Array:
    """⟨e_x, e_C⟩ for a batch: (B, h) -> (B, L), at full f32 precision
    on every backend (the fused dispatch kernel's precision too)."""
    return jnp.matmul(x.astype(jnp.float32), selector.embeddings.T,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("block",))
def doc_scores(selector: ClusterSelector, doc_embeddings: Array,
               doc_assign: Optional[Array] = None, *,
               block: int = DOC_BLOCK) -> tuple[Array, Array]:
    """Indexing side, one ``(block, L)`` tile of the score plane at a
    time: each document's cluster — its argmax, or ``doc_assign`` when
    given (the supervised path's frozen φ(D)) — and its score
    ⟨e_D, e_C⟩ for that cluster.  Returns ((n,) i32, (n,) f32)."""
    n = doc_embeddings.shape[0]
    given = doc_assign is not None
    assign = (jnp.asarray(doc_assign, jnp.int32) if given
              else jnp.zeros((n,), jnp.int32))

    def one_block(args):
        xi, ai = args
        s = scores(selector, xi)                             # (block, L)
        if not given:
            ai = jnp.argmax(s, axis=-1).astype(jnp.int32)
        return ai, jnp.take_along_axis(s, ai[:, None], axis=-1)[:, 0]

    return kmeans.map_blocks(one_block, (doc_embeddings, assign),
                             min(block, n))


def select_for_doc(selector: ClusterSelector, doc_embeddings: Array) -> Array:
    """Indexing side: each document goes to exactly one cluster."""
    return doc_scores(selector, doc_embeddings)[0]


@functools.partial(jax.jit, static_argnames=("k", "use_kernel"))
def select_for_query(selector: ClusterSelector, query_embeddings: Array,
                     k: int, *, use_kernel: bool = False
                     ) -> tuple[Array, Array]:
    """Search side (Eq. 6): top-K^C clusters per query.

    ``use_kernel`` routes through the fused running-top-k kernel —
    bit-identical list ids (same ``lax.top_k`` tie-break, asserted by
    tests/test_kernels.py)."""
    if use_kernel:
        from repro.kernels.assign_topk import ops as at_ops
        top_s, top_i = at_ops.topk_scores(
            query_embeddings.astype(jnp.float32), selector.embeddings, k)
        return top_i, top_s
    s = scores(selector, query_embeddings)
    top_s, top_i = jax.lax.top_k(s, k)
    return top_i.astype(jnp.int32), top_s
