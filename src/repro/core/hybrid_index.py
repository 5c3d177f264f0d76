"""HI² — the Hybrid Inverted Index (paper §4, Eq. 5).

Each document is referenced from the inverted lists of exactly **1
embedding cluster** and **K₁ᵀ salient terms**.  A query is dispatched to
**K^C clusters** and **≤ K₂ᵀ terms**; candidates from both list families
are merged, deduplicated, optionally filtered, scored by the codec and
the top-R returned.

The codec — how documents are stored and scored — is pluggable
(:mod:`repro.core.codecs`, DESIGN.md §7): ``HybridIndex.codec`` is a
spec string (static pytree field, so checkpoints and jit caches stay
stable) resolved through the codec registry; the codec's replicated
parameters and per-document planes live in ``codec_params`` /
``doc_planes`` and are treated opaquely here.

Search-time compute is the staged query-execution engine of
:mod:`repro.core.exec` (DESIGN.md §9):

    dispatch → gather → dedup → filter → score → topk → refine

configured with ONE :class:`~repro.core.exec.Source` (this index's two
list families over its codec planes).  The mutable variant
(:mod:`repro.core.segments`) adds a delta Source; the document-sharded
variants (:mod:`repro.core.sharded_index`) run the same engine inside
``shard_map`` — all four produce bit-identical results because selection
always goes through the total order of :func:`topk_by_score`.

``search(..., filter=)`` takes a per-query namespace bitmap
(:mod:`repro.core.exec.filters`) over the optional ``doc_ns`` plane —
first-class filtered search (tenants, collections) with the same fixed
shapes.  The index build runs once on host+device; searching never
reshapes.  The static per-query candidate count
(:func:`candidate_budget`, one cost model in ``repro.core.exec.cost``)
is the latency proxy used throughout ``benchmarks/``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import cluster_selector as cs_mod
from repro.core import codecs
from repro.core import exec as qexec
from repro.core import inverted_lists as il
from repro.core import term_selector as ts_mod
from repro.core.inverted_lists import PAD_DOC, PaddedLists

Array = jax.Array

# the search-result contract and total-order selection primitive live in
# the exec layer now; re-exported here because every consumer of an
# index naturally imports them from the index module
SearchResult = qexec.SearchResult
topk_by_score = qexec.topk_by_score


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["cluster_sel", "term_sel", "cluster_lists", "term_lists",
                 "codec_params", "doc_planes", "doc_assign", "doc_ns",
                 "sparse_weights"],
    meta_fields=["codec", "tuned"])
@dataclasses.dataclass(frozen=True)
class HybridIndex:
    cluster_sel: cs_mod.ClusterSelector
    term_sel: ts_mod.TermSelector
    cluster_lists: PaddedLists
    term_lists: PaddedLists
    codec_params: Any               # replicated codec state (may be None)
    doc_planes: dict                # per-doc planes, every leaf (n_docs, ...)
    doc_assign: Array               # φ(D), (n_docs,) i32
    doc_ns: Optional[Array] = None  # (n_docs,) i32 namespace ids (filtered
    #                                 search; None ⇒ index is unfiltered)
    sparse_weights: Optional[Array] = None  # (V, Ct) f32 BM25 impact plane
    #                                 aligned with term_lists.entries
    #                                 (build(sparse=True), DESIGN.md §13)
    codec: str = codecs.DEFAULT     # registry spec (static)
    tuned: Optional[qexec.TunedWidths] = None  # autotuned widths (static
    #                                 metadata like codec; DESIGN.md §14)

    @property
    def n_docs(self) -> int:
        return int(self.doc_assign.shape[0])

    # convenience views of the codec planes (None when absent)
    @property
    def doc_codes(self) -> Optional[Array]:
        return self.doc_planes.get("codes")

    @property
    def doc_embeddings(self) -> Optional[Array]:
        return self.doc_planes.get("emb")


# --------------------------------------------------------------------------
# build
# --------------------------------------------------------------------------

@spans.span("hi2.build")
def build(key: Array,
          doc_embeddings: Array,
          doc_tokens: Array,
          vocab_size: int,
          *,
          n_clusters: int,
          k1_terms: int,
          codec: str = codecs.DEFAULT,
          pq_m: int = 8,
          pq_k: int = 256,
          cluster_capacity: Optional[int] = None,
          term_capacity: Optional[int] = None,
          cluster_sel: Optional[cs_mod.ClusterSelector] = None,
          doc_assign: Optional[Array] = None,
          term_pos_scores: Optional[Array] = None,
          term_sel: Optional[ts_mod.TermSelector] = None,
          kmeans_iters: int = 15,
          use_clusters: bool = True,
          use_terms: bool = True,
          doc_namespaces: Optional[Array] = None,
          sparse: bool = False,
          ) -> HybridIndex:
    """Build HI² over a corpus.

    The unsupervised path computes everything here (KMeans + BM25 +
    codec training).  The supervised path passes pre-trained
    ``cluster_sel`` / ``term_pos_scores`` / ``term_sel`` from the
    distillation trainer and reuses the same list construction.
    ``use_clusters`` / ``use_terms`` expose the paper's ablations
    (w.o. Clus / w.o. Term, §5.3).  ``codec`` is any
    :func:`repro.core.codecs.get` spec (unknown names raise with the
    registered list).  ``doc_namespaces`` ((n_docs,) int ids) enables
    per-query filtered search (DESIGN.md §9).  ``sparse=True``
    additionally materializes the BM25 impact plane next to the term
    lists, enabling hybrid search via ``search(fusion=...)``
    (DESIGN.md §13); without it, fusion requests fall back to the
    dense-only result.

    Host spans (DESIGN.md §9): ``hi2.build`` over ``hi2.build.kmeans``
    (when it trains the centres), ``hi2.build.cluster_lists``,
    ``hi2.build.term_lists`` (the BM25 fit and the lists) and
    ``hi2.build.codec`` › ``.codec.train``, ``.codec.encode``.
    """
    codec_impl = codecs.get(codec)    # fail fast on unknown specs
    if sparse and not use_terms:
        raise ValueError("sparse=True needs the term lists "
                         "(use_terms=True): the sparse path scores over "
                         "the term postings")
    n_docs, _ = doc_embeddings.shape
    if doc_namespaces is not None:    # fail fast BEFORE kmeans/codec train
        doc_namespaces = jnp.asarray(doc_namespaces, jnp.int32)
        if doc_namespaces.shape != (n_docs,):
            raise ValueError(
                f"doc_namespaces must be ({n_docs},), got "
                f"{doc_namespaces.shape}")
        if int(doc_namespaces.min()) < 0:
            raise ValueError("doc_namespaces must be non-negative ids")
    k_cl, k_codec, k_ts = jax.random.split(key, 3)

    # --- cluster side -----------------------------------------------------
    if cluster_sel is None:
        cluster_sel, doc_assign = cs_mod.init_kmeans(
            k_cl, doc_embeddings, n_clusters, n_iters=kmeans_iters)
    elif doc_assign is None:
        doc_assign = cs_mod.select_for_doc(cluster_sel, doc_embeddings)

    with spans.span("hi2.build.cluster_lists"):
        if use_clusters:
            _, assign_scores = cs_mod.doc_scores(cluster_sel,
                                                 doc_embeddings, doc_assign)
            cluster_lists = il.build(np.arange(n_docs),
                                     np.asarray(doc_assign),
                                     np.asarray(assign_scores),
                                     n_lists=n_clusters,
                                     capacity=cluster_capacity)
        else:
            cluster_lists = il.PaddedLists(
                entries=jnp.full((n_clusters, 1), PAD_DOC, jnp.int32),
                lengths=jnp.zeros((n_clusters,), jnp.int32))

    # --- term side --------------------------------------------------------
    with spans.span("hi2.build.term_lists"):
        if term_sel is None or term_pos_scores is None:
            term_sel, term_pos_scores, _ = ts_mod.fit_unsup(doc_tokens,
                                                            vocab_size)
        sparse_weights = None
        if use_terms:
            term_ids, term_scores = ts_mod.doc_terms(
                doc_tokens, term_pos_scores, k1_terms)
            doc_rep = np.repeat(np.arange(n_docs), k1_terms)
            if sparse:
                term_lists, sparse_weights = il.build_scored(
                    doc_rep, np.asarray(term_ids).reshape(-1),
                    np.asarray(term_scores).reshape(-1),
                    n_lists=vocab_size, capacity=term_capacity)
            else:
                term_lists = il.build(
                    doc_rep, np.asarray(term_ids).reshape(-1),
                    np.asarray(term_scores).reshape(-1),
                    n_lists=vocab_size, capacity=term_capacity)
        else:
            term_lists = il.PaddedLists(
                entries=jnp.full((vocab_size, 1), PAD_DOC, jnp.int32),
                lengths=jnp.zeros((vocab_size,), jnp.int32))

    # --- codec ------------------------------------------------------------
    # each span ends where its work does
    with spans.span("hi2.build.codec"):
        with spans.span("hi2.build.codec.train"):
            codec_params = codec_impl.train(k_codec, doc_embeddings,
                                            pq_m=pq_m, pq_k=pq_k)
            if spans.recording():
                jax.block_until_ready(codec_params)
        with spans.span("hi2.build.codec.encode"):
            doc_planes = codec_impl.encode(codec_params, doc_embeddings)
            if spans.recording():
                jax.block_until_ready(doc_planes)

    return HybridIndex(cluster_sel=cluster_sel, term_sel=term_sel,
                       cluster_lists=cluster_lists, term_lists=term_lists,
                       codec_params=codec_params, doc_planes=doc_planes,
                       doc_assign=jnp.asarray(doc_assign, jnp.int32),
                       doc_ns=doc_namespaces,
                       sparse_weights=sparse_weights,
                       codec=codec)


# --------------------------------------------------------------------------
# search — one exec.Source over this index
# --------------------------------------------------------------------------

def base_source(index: HybridIndex) -> qexec.Source:
    """The index as a single query-execution gather source."""
    return qexec.Source(cluster_lists=index.cluster_lists,
                        term_lists=index.term_lists,
                        doc_planes=index.doc_planes,
                        size=index.n_docs,
                        doc_ns=index.doc_ns,
                        sparse_weights=index.sparse_weights)


@functools.partial(jax.jit,
                   static_argnames=("kc", "k2", "top_r", "use_kernel",
                                    "fusion"))
def search(index: HybridIndex, query_embeddings: Array, query_tokens: Array,
           *, kc: int, k2: int, top_r: int, use_kernel: bool = False,
           filter: Optional[Array] = None,
           fusion: Optional[qexec.FusionSpec] = None) -> SearchResult:
    """Eq. 5: A(Q) = A^C(Q) ∪ A^T(Q), then codec scoring + top-R —
    executed as the §9 stage chain over one Source.

    ``filter`` is an optional (B, W) uint32 per-query namespace bitmap
    (:func:`repro.core.exec.filters.make_filter`); it needs an index
    built with ``doc_namespaces=``.  ``fusion`` (a static
    :class:`~repro.core.exec.FusionSpec`) enables hybrid dense∥sparse
    search over an index built with ``sparse=True`` (DESIGN.md §13);
    on an index without the impact plane it falls back to the dense
    result, bit-identically.
    """
    return qexec.execute(
        codecs.get(index.codec), index.codec_params,
        index.cluster_sel, index.term_sel, [base_source(index)],
        query_embeddings, query_tokens,
        kc=kc, k2=k2, top_r=top_r, use_kernel=use_kernel,
        ns_filter=filter, fusion=fusion)


def candidate_budget(index: HybridIndex, kc: int, k2: int) -> int:
    """Static per-query candidate slots — the latency proxy used by
    ``benchmarks/`` (DESIGN.md §2; one cost model for every variant in
    :mod:`repro.core.exec.cost`)."""
    return qexec.candidate_budget(
        kc, k2, [(index.cluster_lists.capacity, index.term_lists.capacity)])


def candidate_cost(index: HybridIndex, kc: int, k2: int, top_r: int) -> int:
    """:func:`candidate_budget` plus the codec's refine work — the full
    per-query latency proxy (DESIGN.md §7)."""
    return qexec.candidate_cost(
        index.codec, kc, k2, top_r,
        [(index.cluster_lists.capacity, index.term_lists.capacity)])


def with_tuned(index: HybridIndex,
               tuned: Optional[qexec.TunedWidths]) -> HybridIndex:
    """The index with ``tuned`` width metadata attached (DESIGN.md §14).
    Pure metadata: the doc planes are shared, only the static pytree
    field changes (so the first search re-traces, like a codec swap)."""
    return dataclasses.replace(index, tuned=tuned)


# --------------------------------------------------------------------------
# paper baselines — degenerate configurations of the same machinery
# (folded in from the retired standalone IVF wrappers in PR 4; §5.1
# baselines and §5.3 ablations)
# --------------------------------------------------------------------------

def build_ivf(key: Array, doc_embeddings: Array, doc_tokens: Array,
              vocab_size: int, *, n_clusters: int, codec: str = "opq",
              pq_m: int = 8, pq_k: int = 256,
              cluster_capacity: Optional[int] = None,
              cluster_sel=None, doc_assign=None,
              kmeans_iters: int = 15) -> HybridIndex:
    """Cluster-only index (IVF-Flat / IVF-PQ / IVF-OPQ / Distill-VQ
    body).  Same code path as HI² with the term lists disabled, which
    keeps the comparison honest: identical gather/dedup/top-k machinery,
    only the dispatched lists differ (§5.1)."""
    return build(key, doc_embeddings, doc_tokens, vocab_size,
                 n_clusters=n_clusters, k1_terms=1, codec=codec,
                 pq_m=pq_m, pq_k=pq_k, cluster_capacity=cluster_capacity,
                 cluster_sel=cluster_sel, doc_assign=doc_assign,
                 kmeans_iters=kmeans_iters,
                 use_clusters=True, use_terms=False)


def build_term_only(key: Array, doc_embeddings: Array, doc_tokens: Array,
                    vocab_size: int, *, k1_terms: int, codec: str = "opq",
                    pq_m: int = 8, pq_k: int = 256,
                    term_capacity: Optional[int] = None,
                    term_pos_scores=None, term_sel=None) -> HybridIndex:
    """Term-only index (the paper's w.o. Clus ablation)."""
    return build(key, doc_embeddings, doc_tokens, vocab_size,
                 n_clusters=1, k1_terms=k1_terms, codec=codec,
                 pq_m=pq_m, pq_k=pq_k, term_capacity=term_capacity,
                 term_pos_scores=term_pos_scores, term_sel=term_sel,
                 use_clusters=False, use_terms=True)


def search_ivf(index: HybridIndex, query_embeddings: Array,
               query_tokens: Array, *, kc: int, top_r: int,
               use_kernel: bool = False) -> SearchResult:
    """Search with the term side off (k2=1 dispatches only PAD lists)."""
    return search(index, query_embeddings, query_tokens,
                  kc=kc, k2=1, top_r=top_r, use_kernel=use_kernel)


def search_term_only(index: HybridIndex, query_embeddings: Array,
                     query_tokens: Array, *, k2: int, top_r: int,
                     use_kernel: bool = False) -> SearchResult:
    return search(index, query_embeddings, query_tokens,
                  kc=1, k2=k2, top_r=top_r, use_kernel=use_kernel)
