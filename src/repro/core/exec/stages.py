"""The staged query-execution engine (DESIGN.md §9).

Every HI² search variant — single-device, mutable (base + delta),
document-sharded, and sharded-mutable — is the SAME fixed-shape pipeline

    dispatch → gather → dedup → filter → score → topk → refine

over a different *configuration* of :class:`Source`s (where candidates
come from and which doc planes score them) and an optional
:class:`ShardEnv` (whether a cross-shard merge collective sits between
selection and refine).  This module owns the one implementation of each
stage; the index modules shrink to building the source list and calling
:func:`execute` inside their jitted/shard_map'd bodies.

Bit-identity across variants falls out of three invariants the stages
enforce (DESIGN.md §6/§9):

  · candidate order is source-major, [cluster | term] within a source,
    so any partitioning of the same lists concatenates to a permutation
    of the same (score, id) multiset;
  · top-R selection always goes through :func:`topk_by_score`'s total
    order (score desc, id asc) — a pure function of that multiset;
  · the filter stage (tombstones + per-query namespace bitmaps) masks
    to ``-inf`` BEFORE selection, so no masked doc can reach the
    refine frontier on any variant.

The engine is called *inside* jit / shard_map: sources may carry traced
offsets (``axis_index * per``) and the structures here are plain Python
containers built during tracing, never pytrees.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import cluster_selector as cs_mod
from repro.core import inverted_lists as il
from repro.core import term_selector as ts_mod
from repro.core.codecs import base as codecs_base
from repro.core.exec import filters
from repro.core.exec import fusion as fusion_mod
from repro.core.exec.fusion import FusionSpec
from repro.core.inverted_lists import PAD_DOC, PaddedLists

Array = jax.Array


class SearchResult(NamedTuple):
    doc_ids: Array        # (B, R) i32, PAD_DOC when fewer candidates
    scores: Array         # (B, R) f32
    n_candidates: Array   # (B,) i32 — unique live docs evaluated (∝ QL)
    #: False on every full-index search.  The degraded serving path
    #: (DESIGN.md §12) sets it True host-side when one or more index
    #: shards are ejected, so results cover the surviving document
    #: ranges only — a contract flag, never a traced value.
    partial: Any = False


@dataclasses.dataclass(frozen=True)
class Source:
    """One gather+score source: a (cluster, term) inverted-list family
    over one set of codec doc planes, plus the global→local id mapping.

    ``offset`` is the global doc id stored at local row 0 (0 on the
    single-device base; ``axis_index * per`` under shard_map; shifted by
    ``n_base`` for delta segments) — it may be a traced scalar.
    ``family_lo``/``family_hi`` bound the *global* id range of the whole
    family this source is a slice of (base docs vs delta slots), which
    is what routes refine-stage gathers when several families coexist.
    ``tombstones``/``doc_ns`` are optional per-row planes consumed by
    the filter stage.  ``sparse_weights`` is the BM25 impact plane
    aligned with ``term_lists.entries`` (DESIGN.md §13,
    :func:`repro.core.inverted_lists.build_scored`); when every source
    carries one, ``execute(fusion=...)`` can run the sparse query path.
    """
    cluster_lists: PaddedLists
    term_lists: PaddedLists
    doc_planes: dict
    size: int                                # local rows in each plane
    offset: Union[int, Array] = 0
    family_lo: int = 0
    family_hi: Optional[int] = None          # default: family_lo + size
    tombstones: Optional[Array] = None       # (size,) bool
    doc_ns: Optional[Array] = None           # (size,) i32 namespace ids
    sparse_weights: Optional[Array] = None   # (V, Ct) f32 BM25 impacts

    @property
    def hi_bound(self):
        """Upper bound on global ids this source may own (``family_hi``
        when the family is larger than this source's slice)."""
        return (self.offset + self.size if self.family_hi is None
                else self.family_hi)


@dataclasses.dataclass(frozen=True)
class ShardEnv:
    """Marks execution inside shard_map: sources hold one shard's rows
    and the frontier must merge across ``axis_name`` before refine."""
    axis_name: str


@dataclasses.dataclass
class Frontier:
    """The per-stage state threaded through the pipeline: the candidate
    id plane plus each source's local-row view of its block of it
    (block s is ``local[s]``'s contiguous slice of the cand axis, in
    source order)."""
    cands: Array                   # (B, C) global ids, PAD_DOC invalid
    local: tuple                   # per-source (B, C_s) local rows
    live: Optional[Array] = None   # (B, C) bool after dedup+filter
    scores: Optional[Array] = None  # (B, C) f32, -inf where masked


# --------------------------------------------------------------------------
# selection primitive (shared by topk + every merge)
# --------------------------------------------------------------------------

def topk_by_score(scores: Array, ids: Array, r: int) -> tuple[Array, Array]:
    """Top-r rows under the total order (score desc, doc id asc).

    ``jax.lax.top_k`` breaks score ties by *position* in the candidate
    array, which differs between candidate orderings (single-device
    concat vs per-shard merge).  Sorting on the composite key makes the
    selection a pure function of the (score, id) *set*, so any
    partitioning of the candidates merges back bit-identically
    (DESIGN.md §6).  Invalid slots must carry ``-inf`` scores; they sort
    last and keep their raw ids — callers mask them (``isfinite``).
    Returns ``(scores, ids)`` of shape (B, r), ``-inf``/``PAD_DOC``
    filled when fewer than r slots exist.
    """
    k_eff = min(r, scores.shape[-1])
    neg_s, sorted_ids = jax.lax.sort(
        (-scores, ids), dimension=-1, num_keys=2)
    top_s, top_ids = -neg_s[..., :k_eff], sorted_ids[..., :k_eff]
    if k_eff < r:
        pad = ((0, 0), (0, r - k_eff))
        top_s = jnp.pad(top_s, pad, constant_values=-jnp.inf)
        top_ids = jnp.pad(top_ids, pad, constant_values=PAD_DOC)
    return top_s, top_ids


# --------------------------------------------------------------------------
# stages
# --------------------------------------------------------------------------

def dispatch(cluster_sel: cs_mod.ClusterSelector,
             term_sel: ts_mod.TermSelector,
             query_embeddings: Array, query_tokens: Array,
             kc: int, k2: int, use_kernel: bool = False
             ) -> tuple[Array, Array]:
    """Query → K^C cluster list ids + ≤K₂ᵀ term list ids (Eq. 5 LHS).

    Under ``use_kernel`` the cluster top-k runs through the
    ``kernels/assign_topk`` running-top-k kernel (bit-identical ids to
    the ``lax.top_k`` path — same tie-break, asserted by
    tests/test_kernels.py)."""
    cluster_ids, _ = cs_mod.select_for_query(cluster_sel,
                                             query_embeddings, kc,
                                             use_kernel=use_kernel)
    term_ids = ts_mod.query_terms(term_sel, query_tokens, k2)
    return cluster_ids, term_ids


def gather(sources: Sequence[Source], cluster_ids: Array,
           term_ids: Array) -> Frontier:
    """Fetch every source's dispatched list rows into one candidate
    plane (source-major, [cluster | term] within a source) and record
    each source's local-row view of its block."""
    pieces, local = [], []
    for s in sources:
        c = jnp.concatenate(
            [il.gather_candidates(s.cluster_lists, cluster_ids),
             il.gather_candidates(s.term_lists, term_ids)], axis=-1)
        pieces.append(c)
        local.append(jnp.clip(c - s.offset, 0, s.size - 1))
    cands = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, -1)
    return Frontier(cands=cands, local=tuple(local))


def dedup(frontier: Frontier) -> Array:
    """First-occurrence mask over the whole candidate plane.  Sources
    own disjoint global id ranges, so this is global set semantics no
    matter how the corpus is partitioned."""
    return il.dedup_mask(frontier.cands)


def filter_stage(frontier: Frontier, sources: Sequence[Source],
                 keep: Array, ns_filter: Optional[Array]) -> Array:
    """keep ∧ ¬tombstoned ∧ namespace-allowed, per candidate slot.

    Runs between dedup and score (DESIGN.md §9): a filtered doc carries
    ``-inf`` into selection, so it can never consume a top-R′ slot or
    resurface through the refine stage — tombstones (per-doc, from the
    mutation layer) and per-query namespace bitmaps (``ns_filter``,
    built by :mod:`repro.core.exec.filters`) are the same mechanism at
    different granularities.
    """
    live = keep
    if any(s.tombstones is not None for s in sources):
        dead = [
            (s.tombstones[loc] if s.tombstones is not None
             else jnp.zeros(loc.shape, bool))
            for s, loc in zip(sources, frontier.local)]
        dead = dead[0] if len(dead) == 1 else jnp.concatenate(dead, -1)
        live = live & ~dead
    if ns_filter is not None:
        missing = [i for i, s in enumerate(sources) if s.doc_ns is None]
        if missing:
            raise ValueError(
                "search(filter=...) needs namespace planes on every "
                f"source, but source(s) {missing} have none — build the "
                "index with doc_namespaces= (hybrid_index.build) / pass "
                "namespaces= to add_docs")
        ns = [s.doc_ns[loc] for s, loc in zip(sources, frontier.local)]
        ns = ns[0] if len(ns) == 1 else jnp.concatenate(ns, -1)
        live = live & filters.allowed_mask(ns_filter, ns)
    return live


def score(codec_impl: codecs_base.Codec, codec_params: Any,
          sources: Sequence[Source], frontier: Frontier, live: Array,
          query_embeddings: Array, use_kernel: bool) -> Array:
    """Codec-score each source's block against its own doc planes;
    masked slots carry ``-inf`` into selection.

    Each scorer receives its source's static-width slice of ``live``
    and owns the mask-to-``-inf`` (fused kernels apply it in-kernel —
    DESIGN.md §11).  Slicing + per-part masking + concat is elementwise-
    identical to masking the concatenated plane, so this refactor is
    bitwise-neutral for the unfused path."""
    parts, off = [], 0
    for s, loc in zip(sources, frontier.local):
        w = loc.shape[-1]
        parts.append(
            codec_impl.make_scorer(codec_params, s.doc_planes,
                                   query_embeddings, use_kernel)
            (loc, live[..., off:off + w]))
        off += w
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def topk(frontier: Frontier, r_prime: int,
         shard: Optional[ShardEnv]) -> tuple[Array, Array]:
    """Total-order top-R′ selection; under a :class:`ShardEnv` the
    per-shard frontiers all-gather and re-select, which the total order
    makes bit-identical to selecting over the concatenated candidates
    (DESIGN.md §6)."""
    top_s, top_ids = topk_by_score(frontier.scores, frontier.cands, r_prime)
    if shard is not None:
        from repro.distributed import collectives
        all_s, all_ids = collectives.gather_topk(top_s, top_ids,
                                                 shard.axis_name)
        top_s, top_ids = topk_by_score(all_s, all_ids, r_prime)
    return top_s, top_ids


def sparse_topk(sources: Sequence[Source], term_ids: Array, r: int,
                ns_filter: Optional[Array], shard: Optional[ShardEnv]
                ) -> tuple[Array, Array, Array]:
    """The sparse (BM25) query path (DESIGN.md §13): top-r documents by
    summed term impact over the ≤K₂ᵀ *dispatched* term lists.

    Reuses the dense dispatch's ``term_ids`` — sparse and dense probe
    the same lists — and each source's impact plane
    (``Source.sparse_weights``, aligned with ``term_lists.entries``).
    Per source: gather the probed postings + impacts, mask tombstoned /
    namespace-filtered docs to (PAD_DOC, 0) — the same fail-closed
    semantics as the dense filter stage — then sum impacts per unique
    document (:func:`repro.core.exec.fusion.sum_by_doc`) and select
    through the same total order as every other stage.  Zero-total
    documents (only zero-impact postings matched) rank as non-matches.

    Under a :class:`ShardEnv` each shard owns all of a document's
    postings, so per-shard sums equal single-device sums bit-exactly
    and the §6 gather + re-select merge applies unchanged.  Returns
    ``(scores, ids, n_sparse)`` — (B, r) planes (``-inf``/PAD_DOC
    padded) plus the unique matched-doc count per query.
    """
    ids_parts, w_parts = [], []
    for s in sources:
        safe = jnp.clip(term_ids, 0, None)
        rows = s.term_lists.entries[safe]             # (B, K2, Ct)
        w = s.sparse_weights[safe]
        probed = (term_ids >= 0)[:, :, None]
        ids = jnp.where(probed, rows, PAD_DOC).reshape(rows.shape[0], -1)
        w = jnp.where(probed, w, 0.0).reshape(ids.shape)
        live = ids != PAD_DOC
        loc = jnp.clip(ids - s.offset, 0, s.size - 1)
        if s.tombstones is not None:
            live = live & ~s.tombstones[loc]
        if ns_filter is not None:
            live = live & filters.allowed_mask(ns_filter, s.doc_ns[loc])
        ids_parts.append(jnp.where(live, ids, PAD_DOC))
        w_parts.append(jnp.where(live, w, 0.0))
    ids = (ids_parts[0] if len(ids_parts) == 1
           else jnp.concatenate(ids_parts, -1))
    w = w_parts[0] if len(w_parts) == 1 else jnp.concatenate(w_parts, -1)
    sid, totals, first = fusion_mod.sum_by_doc(ids, w)
    rep = first & (sid != PAD_DOC) & (totals > 0.0)
    scores = jnp.where(rep, totals, -jnp.inf)
    n_sparse = rep.sum(axis=-1).astype(jnp.int32)
    top_s, top_ids = topk_by_score(scores, sid, r)
    if shard is not None:
        from repro.distributed import collectives
        n_sparse = jax.lax.psum(n_sparse, shard.axis_name)
        all_s, all_ids = collectives.gather_topk(top_s, top_ids,
                                                 shard.axis_name)
        top_s, top_ids = topk_by_score(all_s, all_ids, r)
    return top_s, top_ids, n_sparse


def fuse(dense_scores: Array, dense_ids: Array, sparse_scores: Array,
         sparse_ids: Array, fusion: FusionSpec, top_r: int
         ) -> tuple[Array, Array]:
    """Reciprocal-rank fusion of the final dense and sparse rankings
    (DESIGN.md §13): contribution ``weight/(rrf_k+1+rank)`` from the
    dense list, ``(1−weight)/(rrf_k+1+rank)`` from the sparse one,
    summed per document, ties broken by ascending doc id via
    :func:`topk_by_score`.

    Runs strictly AFTER the shard merge (both inputs are the already
    replicated (B, R) planes), mirroring the §7 refine argument: ranks
    are positions in the merged total order, so every shard fuses the
    identical lists and the fused result needs no further collective.
    At ``weight=1.0`` sparse contributions are exactly 0.0 and
    sparse-only docs mask out, so fused doc ids are bit-identical to
    the dense-only search; ``weight=0.0`` is symmetric for sparse.
    """
    d = fusion_mod.rrf_contributions(dense_scores, fusion.weight,
                                     fusion.rrf_k)
    sp = fusion_mod.rrf_contributions(sparse_scores, 1.0 - fusion.weight,
                                      fusion.rrf_k)
    ids = jnp.concatenate(
        [jnp.where(jnp.isfinite(dense_scores), dense_ids, PAD_DOC),
         jnp.where(jnp.isfinite(sparse_scores), sparse_ids, PAD_DOC)], -1)
    vals = jnp.concatenate([d, sp], -1)
    sid, totals, first = fusion_mod.sum_by_doc(ids, vals)
    live = first & (sid != PAD_DOC) & (totals > 0.0)
    return topk_by_score(jnp.where(live, totals, -jnp.inf), sid, top_r)


# --------------------------------------------------------------------------
# refine plumbing: route frontier ids back to the owning source
# --------------------------------------------------------------------------

def _route_gather(sources: Sequence[Source], plane_group, ids: Array
                  ) -> Array:
    """Gather rows for global ``ids`` from per-source planes, routing
    each id to the source family that stores it (ids below the second
    family's ``family_lo`` hit the first, and so on).  Out-of-source
    rows are clipped garbage — callers mask via ``owned`` /
    finite-score checks."""
    if len(sources) == 1:
        s = sources[0]
        return plane_group[jnp.clip(ids - s.offset, 0, s.size - 1)]
    rows = None
    for s, plane in zip(sources, plane_group):
        mine = plane[jnp.clip(ids - s.offset, 0, s.size - 1)]
        if rows is None:
            rows = mine
            continue
        is_here = ids >= s.family_lo
        is_here = is_here.reshape(
            is_here.shape + (1,) * (mine.ndim - is_here.ndim))
        rows = jnp.where(is_here, mine, rows)
    return rows


def refine_planes(sources: Sequence[Source]) -> dict:
    """The doc-plane pytree handed to ``codec.refine``: the planes
    themselves for one source, per-key tuples of per-source planes
    otherwise (opaque to the codec — ``ctx.gather`` routes them)."""
    if len(sources) == 1:
        return sources[0].doc_planes
    return {k: tuple(s.doc_planes[k] for s in sources)
            for k in sources[0].doc_planes}


def make_refine_ctx(sources: Sequence[Source],
                    shard: Optional[ShardEnv]) -> codecs_base.RefineCtx:
    """RefineCtx over any source list: gathers route by family range,
    ``owned`` is the union of each source's local id range (so each doc
    is scored by exactly one shard), psum assembles across shards."""
    def gather_fn(plane_group, ids):
        return _route_gather(sources, plane_group, ids)

    def owned(ids):
        mask = None
        for s in sources:
            m = ((ids >= s.offset) & (ids < s.offset + s.size)
                 & (ids < s.hi_bound))
            mask = m if mask is None else (mask | m)
        return mask

    if shard is None:
        psum = lambda x: x                                    # noqa: E731
    else:
        axis = shard.axis_name
        psum = lambda x: jax.lax.psum(x, axis)                # noqa: E731
    return codecs_base.RefineCtx(gather=gather_fn, owned=owned, psum=psum)


# --------------------------------------------------------------------------
# compile accounting
# --------------------------------------------------------------------------

# :func:`execute` runs only while jax traces a search program (every
# variant's jitted/shard_map'd body funnels through it, and one compile
# traces it exactly once — asserted by tests/test_runtime.py), so the
# number of calls IS the number of search programs compiled in this
# process: +1 per new (variant, batch shape, static config) signature,
# +0 on jit-cache hits.  The serving runtime (repro.launch.runtime)
# reads deltas of this counter to enforce its one-compile-per-bucket
# warmup contract (DESIGN.md §10).
_TRACES = 0


def trace_count() -> int:
    """Search programs traced (≈ compiled) so far in this process."""
    return _TRACES


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

#: the stages of :func:`execute`, each traced under the name scope
#: ``hi2.<stage>``: the scope rides into the compiled program's
#: ``op_name`` metadata and the profiler's op names, so device time can
#: be split by stage (DESIGN.md §9); it changes nothing else
STAGES = ("dispatch", "gather", "dedup", "filter", "score", "topk",
          "refine", "sparse", "fuse")
SCOPE_PREFIX = "hi2."


def _scope(stage: str):
    return jax.named_scope(SCOPE_PREFIX + stage)


def execute(codec_impl: codecs_base.Codec, codec_params: Any,
            cluster_sel: cs_mod.ClusterSelector,
            term_sel: ts_mod.TermSelector,
            sources: Sequence[Source],
            query_embeddings: Array, query_tokens: Array, *,
            kc: int, k2: int, top_r: int, use_kernel: bool = False,
            ns_filter: Optional[Array] = None,
            shard: Optional[ShardEnv] = None,
            fusion: Optional[FusionSpec] = None) -> SearchResult:
    """Run the full stage chain over ``sources`` (Eq. 5 + DESIGN.md §9).

    One body for all four variants: the single-device immutable path is
    one Source and no ShardEnv; mutable adds a delta Source; the sharded
    paths run this same function inside shard_map with per-shard sources
    and ``shard`` set.  ``ns_filter`` is the per-query namespace bitmap
    of :func:`repro.core.exec.filters.make_filter` (None ⇒ unfiltered).

    ``fusion`` (a :class:`~repro.core.exec.fusion.FusionSpec`, static)
    adds the sparse BM25 path + RRF fusion of DESIGN.md §13 after the
    dense refine; it is honored only when every source carries a
    ``sparse_weights`` impact plane — otherwise the search falls back
    to the dense-only result, unchanged to the bit (the documented
    contract for indexes built without ``sparse=True``).  Under fusion,
    ``scores`` are RRF mass (not codec scores) and ``n_candidates``
    additionally counts the unique sparse-matched docs (a doc seen by
    both paths is counted in each).
    """
    global _TRACES
    _TRACES += 1
    with _scope("dispatch"):
        cluster_ids, term_ids = dispatch(cluster_sel, term_sel,
                                         query_embeddings, query_tokens,
                                         kc, k2, use_kernel)
    with _scope("gather"):
        frontier = gather(sources, cluster_ids, term_ids)
    with _scope("dedup"):
        keep = dedup(frontier)
    with _scope("filter"):
        frontier.live = filter_stage(frontier, sources, keep, ns_filter)
    with _scope("score"):
        frontier.scores = score(codec_impl, codec_params, sources, frontier,
                                frontier.live, query_embeddings, use_kernel)
    with _scope("topk"):
        top_s, top_ids = topk(frontier, codec_impl.refine_width(top_r),
                              shard)
    with _scope("refine"):
        top_s, top_ids = codec_impl.refine(
            codec_params, refine_planes(sources), query_embeddings,
            top_s, top_ids, top_r, make_refine_ctx(sources, shard))

    fused = (fusion is not None
             and all(s.sparse_weights is not None for s in sources))
    if fused:
        with _scope("sparse"):
            sp_s, sp_ids, n_sparse = sparse_topk(sources, term_ids, top_r,
                                                 ns_filter, shard)
        with _scope("fuse"):
            top_s, top_ids = fuse(top_s, top_ids, sp_s, sp_ids, fusion,
                                  top_r)

    # the live-candidate count belongs to the filter stage, the final
    # masking of the answer to the stage that produced it
    with _scope("filter"):
        n_cand = frontier.live.sum(axis=-1).astype(jnp.int32)
        if shard is not None:
            n_cand = jax.lax.psum(n_cand, shard.axis_name)
    if fused:
        with _scope("sparse"):
            n_cand = n_cand + n_sparse
    with _scope("fuse" if fused else "refine"):
        valid = jnp.isfinite(top_s)
        return SearchResult(
            doc_ids=jnp.where(valid, top_ids, PAD_DOC).astype(jnp.int32),
            scores=jnp.where(valid, top_s, 0.0),
            n_candidates=n_cand)
