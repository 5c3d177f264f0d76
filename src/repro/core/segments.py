"""Streaming index mutations — delta segments, tombstones, compaction
(DESIGN.md §8).

The base :class:`~repro.core.hybrid_index.HybridIndex` is build-once:
its planes are immutable and its shapes are baked into the compiled
search program.  Live corpora churn, so this module adds the classic
segment model on top of it without giving up the fixed-shape search
contract of DESIGN.md §2:

    MutableHybridIndex = immutable base + one delta segment + tombstones

    add_docs()     assign through the *frozen* base selectors (cluster
                   argmax, BM25 terms under the base corpus statistics),
                   encode through the base codec params, append into
                   fixed-capacity delta planes.  New docs get global ids
                   ``n_base + slot``.
    delete_docs()  set a tombstone bit; the exec layer's filter stage
                   applies the mask before the total-order top-R
                   selection, so a deleted doc can never surface — not
                   even as a refine-stage candidate.
    compact()      fold the delta into a fresh base.  Implemented as a
                   from-scratch :func:`repro.core.hybrid_index.build`
                   over the surviving corpus with the original key, so
                   the result is bit-identical to rebuilding — the
                   correctness anchor (the §6 sharded-equals-single
                   contract's streaming analogue), enforced for every
                   registered codec by ``tests/test_segments.py``.

Search is the staged query-execution engine of :mod:`repro.core.exec`
(DESIGN.md §9) over TWO gather sources — the base planes and the
fixed-capacity delta planes — merged through the same total-order
selection as every other variant, so every registered codec
(flat/pq/opq/sq8/refine) works unmodified and per-query namespace
filters (``search(..., filter=)``) apply to streamed docs exactly like
indexed ones.  Mutations are host-side numpy (like the base build);
they change plane *values*, never shapes, so serving never recompiles
between compactions.

:class:`ShardedMutableIndex` runs the same semantics over the
document-sharded layout of DESIGN.md §6: each shard owns a contiguous
slice of the delta slots next to its base doc range, adds are routed to
the owning shard by the slot's global id, and the per-shard frontiers
merge through the same total-order collective — bit-identical to the
single-device mutable search.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bm25
from repro.core import cluster_selector as cs_mod
from repro.core import codecs
from repro.core import exec as qexec
from repro.core import hybrid_index as hi
from repro.core import sharded_index as shi
from repro.core import term_selector as ts_mod
from repro.core.inverted_lists import PAD_DOC, PaddedLists

Array = jax.Array


class DeltaFull(RuntimeError):
    """Raised by ``add_docs`` when the delta segment has no free slots;
    call ``compact()`` to fold the delta into a fresh base first."""


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["cluster_lists", "term_lists", "doc_planes", "doc_assign",
                 "doc_ns", "sparse_weights"],
    meta_fields=[])
@dataclasses.dataclass(frozen=True)
class DeltaSegment:
    """The device-side view of the delta: fixed-capacity list planes over
    the same list ids as the base, codec doc planes with ``capacity``
    rows, entries holding *global* doc ids (``n_base + slot``)."""
    cluster_lists: PaddedLists        # (L, Cc') i32
    term_lists: PaddedLists           # (V, Ct') i32
    doc_planes: dict                  # codec planes, leaves (capacity, ...)
    doc_assign: Array                 # (capacity,) i32
    doc_ns: Optional[Array] = None    # (capacity,) i32 namespace ids
    sparse_weights: Optional[Array] = None  # (V, Ct') f32 BM25 impacts,
    #                                   derived from the delta's eviction
    #                                   score plane (DESIGN.md §13)

    @property
    def capacity(self) -> int:
        return int(self.doc_assign.shape[0])


def _pair_sources(base: hi.HybridIndex, delta: DeltaSegment,
                  tombstones: Array) -> list:
    """The (base, delta) source pair for the single-device mutable path:
    same global-capacity base source as the immutable index, plus the
    delta planes owning global ids [n_base, n_base + capacity)."""
    n_base = base.doc_assign.shape[0]
    cap = delta.capacity
    return [
        qexec.Source(cluster_lists=base.cluster_lists,
                     term_lists=base.term_lists,
                     doc_planes=base.doc_planes,
                     size=n_base,
                     tombstones=tombstones[:n_base],
                     doc_ns=base.doc_ns,
                     sparse_weights=base.sparse_weights),
        qexec.Source(cluster_lists=delta.cluster_lists,
                     term_lists=delta.term_lists,
                     doc_planes=delta.doc_planes,
                     size=cap,
                     offset=n_base,
                     family_lo=n_base,
                     family_hi=n_base + cap,
                     tombstones=tombstones[n_base:],
                     doc_ns=delta.doc_ns,
                     sparse_weights=delta.sparse_weights),
    ]


@functools.partial(jax.jit,
                   static_argnames=("kc", "k2", "top_r", "use_kernel",
                                    "fusion"))
def search(base: hi.HybridIndex, delta: DeltaSegment, tombstones: Array,
           query_embeddings: Array, query_tokens: Array, *, kc: int,
           k2: int, top_r: int, use_kernel: bool = False,
           filter: Optional[Array] = None,
           fusion: Optional[qexec.FusionSpec] = None) -> hi.SearchResult:
    """Eq. 5 over base ∪ delta minus tombstones — one fixed-shape jitted
    program (DESIGN.md §8): the §9 stage chain over the (base, delta)
    source pair.

    Dispatch runs once on the shared selectors; base and delta
    candidates are gathered from their own list planes, deduped,
    tombstone- and namespace-masked together, scored by the codec
    against their own doc planes, and the merged frontier goes through
    the total-order selection *before* the codec's refine stage — so
    refine can never resurrect a tombstoned or filtered doc (masked
    slots carry ``-inf`` and stay ``-inf`` through re-ranking).
    ``n_candidates`` counts unique *live* docs evaluated.
    """
    return qexec.execute(
        codecs.get(base.codec), base.codec_params,
        base.cluster_sel, base.term_sel,
        _pair_sources(base, delta, tombstones),
        query_embeddings, query_tokens,
        kc=kc, k2=k2, top_r=top_r, use_kernel=use_kernel,
        ns_filter=filter, fusion=fusion)


# --------------------------------------------------------------------------
# host-side mutable state
# --------------------------------------------------------------------------

def _insert_posting(entries: np.ndarray, scores: np.ndarray,
                    lengths: np.ndarray, list_id: int, doc_id: int,
                    score: float) -> bool:
    """Append one (doc, score) posting to a fixed-capacity delta list.

    Overflow evicts the lowest-scoring posting iff the newcomer beats it
    — the same per-document-score truncation the base build applies
    (DESIGN.md §2), done incrementally.  Returns False when the posting
    was dropped instead.
    """
    cap = entries.shape[1]
    n = int(lengths[list_id])
    if n < cap:
        entries[list_id, n] = doc_id
        scores[list_id, n] = score
        lengths[list_id] = n + 1
        return True
    j = int(np.argmin(scores[list_id]))
    if score <= scores[list_id, j]:
        return False
    entries[list_id, j] = doc_id
    scores[list_id, j] = score
    return True


class MutableHybridIndex:
    """Base HI² + one fixed-capacity delta segment + a tombstone set.

    Construct with :meth:`create` (which also runs the base build), then
    ``add_docs`` / ``delete_docs`` / ``search`` / ``compact``.  Mutation
    is host-side numpy; search operands are rebuilt lazily and cached,
    so repeated searches between mutations transfer nothing.

    The raw corpus (embeddings + tokens + namespaces when filtered) is
    retained host-side: it is the source of truth ``compact()`` rebuilds
    from and what makes the rebuild bit-identical to a from-scratch
    build over the survivors.
    """

    def __init__(self, base: hi.HybridIndex, *, vocab_size: int, key: Array,
                 build_kwargs: dict, delta_capacity: int,
                 delta_cluster_capacity: int, delta_term_capacity: int,
                 corpus_emb: np.ndarray, corpus_tokens: np.ndarray,
                 corpus_ns: Optional[np.ndarray] = None, selectors=None):
        if delta_capacity < 1:
            raise ValueError("delta_capacity must be >= 1")
        self.base = base
        self.vocab_size = int(vocab_size)
        self.key = key
        self.build_kwargs = dict(build_kwargs)
        self.selectors = selectors
        self.delta_capacity = int(delta_capacity)
        self.delta_cluster_capacity = int(delta_cluster_capacity)
        self.delta_term_capacity = int(delta_term_capacity)
        self._corpus_emb = np.array(corpus_emb, np.float32)
        self._corpus_tokens = np.array(corpus_tokens, np.int32)
        if (corpus_ns is None) != (base.doc_ns is None):
            raise ValueError("corpus_ns must accompany a namespaced base")
        self._corpus_ns = (None if corpus_ns is None
                           else np.array(corpus_ns, np.int32))
        self._stats = bm25.fit(jnp.asarray(self._corpus_tokens), vocab_size)

        n_clusters = base.cluster_lists.n_lists
        hidden = self._corpus_emb.shape[1]
        cap = self.delta_capacity
        self._dc_entries = np.full((n_clusters, delta_cluster_capacity),
                                   PAD_DOC, np.int32)
        self._dc_scores = np.full((n_clusters, delta_cluster_capacity),
                                  -np.inf, np.float32)
        self._dc_lengths = np.zeros((n_clusters,), np.int32)
        self._dt_entries = np.full((vocab_size, delta_term_capacity),
                                   PAD_DOC, np.int32)
        self._dt_scores = np.full((vocab_size, delta_term_capacity),
                                  -np.inf, np.float32)
        self._dt_lengths = np.zeros((vocab_size,), np.int32)
        # preallocate codec planes by encoding a zero block — exact
        # shapes/dtypes for any registered codec, no per-codec branches
        codec_impl = codecs.get(base.codec)
        zero = codec_impl.encode(base.codec_params,
                                 jnp.zeros((cap, hidden), jnp.float32))
        self._delta_planes = {k: np.array(v) for k, v in zero.items()}
        self._delta_assign = np.zeros((cap,), np.int32)
        self._delta_ns = (None if self._corpus_ns is None
                          else np.zeros((cap,), np.int32))
        self._delta_emb = np.zeros((cap, hidden), np.float32)
        self._delta_tokens = np.full((cap, self._corpus_tokens.shape[1]),
                                     bm25.PAD_ID, np.int32)
        self._tomb = np.zeros((self.n_base + cap,), bool)
        self._count = 0
        self.dropped_postings = 0
        self._cache: Optional[tuple[DeltaSegment, Array]] = None
        self._epoch = 0

    # --- construction ----------------------------------------------------
    @classmethod
    def create(cls, key: Array, doc_emb, doc_tokens, vocab_size: int, *,
               delta_capacity: int = 1024,
               delta_cluster_capacity: Optional[int] = None,
               delta_term_capacity: Optional[int] = None,
               doc_namespaces=None, selectors=None,
               **build_kwargs) -> "MutableHybridIndex":
        """Build the base index and wrap it with an empty delta segment.

        ``build_kwargs`` are forwarded verbatim to
        :func:`repro.core.hybrid_index.build` — and replayed by
        ``compact()``, so they must be plain JSON-able values
        (ints/strings/bools), not pre-trained selector overrides.
        ``doc_namespaces`` enables filtered search; streamed docs carry
        the ``namespaces=`` argument of :meth:`add_docs`.

        ``selectors`` optionally supplies *supervised* selectors (a
        :class:`repro.launch.train.SupSelectors`): an object with
        ``build_inputs(doc_emb, doc_tokens, vocab_size)`` returning the
        selector overrides for :func:`hi.build` and
        ``position_scores(doc_tokens)`` scoring streamed docs.  Because
        the object is corpus-independent, ``compact()`` can replay the
        build over the survivor set — unlike raw selector arrays, which
        stay rejected below.
        """
        for k in ("cluster_sel", "doc_assign", "term_sel",
                  "term_pos_scores"):
            if k in build_kwargs:
                raise ValueError(
                    f"build_kwargs[{k!r}] is not supported: compact() "
                    "replays the build from scratch and cannot persist "
                    "raw selector arrays — pass a corpus-independent "
                    "``selectors=`` object instead")
        doc_emb = np.asarray(doc_emb, np.float32)
        doc_tokens = np.asarray(doc_tokens, np.int32)
        if doc_namespaces is not None:
            doc_namespaces = np.asarray(doc_namespaces, np.int32)
        sel_kwargs = {}
        if selectors is not None:
            sel_kwargs = selectors.build_inputs(
                jnp.asarray(doc_emb), jnp.asarray(doc_tokens), vocab_size)
            # list count is fixed by the trained selector, not the caller
            n_sel = int(sel_kwargs["cluster_sel"].embeddings.shape[0])
            if build_kwargs.setdefault("n_clusters", n_sel) != n_sel:
                raise ValueError(
                    f"n_clusters={build_kwargs['n_clusters']} conflicts "
                    f"with the supervised selectors' {n_sel} clusters; "
                    "omit n_clusters to derive it")
        base = hi.build(key, jnp.asarray(doc_emb), jnp.asarray(doc_tokens),
                        vocab_size, doc_namespaces=doc_namespaces,
                        **sel_kwargs, **build_kwargs)
        n_clusters = base.cluster_lists.n_lists
        k1 = int(build_kwargs["k1_terms"])
        if delta_cluster_capacity is None:
            delta_cluster_capacity = min(
                delta_capacity,
                max(8, 4 * -(-delta_capacity // n_clusters)))
        if delta_term_capacity is None:
            delta_term_capacity = min(
                delta_capacity,
                max(8, 4 * -(-delta_capacity * k1 // vocab_size)))
        return cls(base, vocab_size=vocab_size, key=key,
                   build_kwargs=build_kwargs, delta_capacity=delta_capacity,
                   delta_cluster_capacity=delta_cluster_capacity,
                   delta_term_capacity=delta_term_capacity,
                   corpus_emb=doc_emb, corpus_tokens=doc_tokens,
                   corpus_ns=doc_namespaces, selectors=selectors)

    # --- views -----------------------------------------------------------
    @property
    def n_base(self) -> int:
        return self.base.n_docs

    @property
    def n_docs(self) -> int:
        """Allocated doc ids (base + filled delta slots), incl. deleted."""
        return self.n_base + self._count

    @property
    def delta_count(self) -> int:
        return self._count

    @property
    def delta_fill(self) -> float:
        return self._count / self.delta_capacity

    @property
    def n_deleted(self) -> int:
        return int(self._tomb[:self.n_docs].sum())

    @property
    def n_live(self) -> int:
        return self.n_docs - self.n_deleted

    @property
    def tombstone_ratio(self) -> float:
        """Deleted fraction of the allocated corpus — with
        :attr:`delta_fill`, one of the two auto-compaction watermarks
        (DESIGN.md §8)."""
        return self.n_deleted / self.n_docs if self.n_docs else 0.0

    def needs_compact(self, fill_watermark: float = 0.0,
                      tombstone_watermark: float = 0.0) -> bool:
        """True when either watermark is crossed: delta fill >=
        ``fill_watermark`` or tombstone ratio >= ``tombstone_watermark``.
        A watermark of 0 disables that trigger (the default — compaction
        stays manual unless serving opts in)."""
        if fill_watermark > 0 and self.delta_fill >= fill_watermark:
            return True
        return (tombstone_watermark > 0
                and self.tombstone_ratio >= tombstone_watermark)

    @property
    def tombstones(self) -> np.ndarray:
        return self._tomb.copy()

    @property
    def filtered(self) -> bool:
        """True when the index carries namespace planes (DESIGN.md §9)."""
        return self._corpus_ns is not None

    @property
    def epoch(self) -> int:
        """Monotonic mutation counter: +1 per ``add_docs`` /
        ``delete_docs`` call and across ``compact()`` (which renumbers
        doc ids, so it must invalidate too).  Serving caches key results
        on it — two searches at the same epoch see the same corpus
        (DESIGN.md §10)."""
        return self._epoch

    def is_deleted(self, ids) -> np.ndarray:
        return self._tomb[np.asarray(ids)]

    def namespaces_of(self, ids) -> np.ndarray:
        """Namespace id of each global doc id (filtered indexes only)."""
        if not self.filtered:
            raise ValueError("index has no namespace planes")
        ids = np.asarray(ids)
        all_ns = np.concatenate([self._corpus_ns, self._delta_ns])
        return all_ns[ids]

    # --- mutation --------------------------------------------------------
    def add_docs(self, doc_emb, doc_tokens, namespaces=None) -> np.ndarray:
        """Append documents to the delta segment; returns their global ids.

        Assignment uses the *frozen* base state: cluster = argmax against
        the base selector, salient terms = BM25 under the base corpus
        statistics (df/avgdl/s̄ refresh only at ``compact()``) — or, on a
        supervised index, the frozen ``selectors`` term scorer.
        ``namespaces`` ((n_new,) int ids or a scalar) is required on a
        filtered index and rejected on an unfiltered one.  Raises
        :class:`DeltaFull` when the segment has no free slots.
        """
        emb = np.atleast_2d(np.asarray(doc_emb, np.float32))
        tokens = np.atleast_2d(np.asarray(doc_tokens, np.int32))
        n_new = emb.shape[0]
        if tokens.shape[0] != n_new:
            raise ValueError(f"emb/tokens row mismatch: {n_new} vs "
                             f"{tokens.shape[0]}")
        if namespaces is not None and not self.filtered:
            raise ValueError(
                "namespaces= on an unfiltered index; build with "
                "doc_namespaces= to enable filtered search")
        if self.filtered:
            if namespaces is None:
                raise ValueError(
                    "filtered index: add_docs needs namespaces= for the "
                    "new docs")
            ns = np.broadcast_to(np.asarray(namespaces, np.int32),
                                 (n_new,)).copy()
            if ns.min() < 0:
                raise ValueError("namespaces must be non-negative ids")
        width = self._corpus_tokens.shape[1]
        if tokens.shape[1] > width:
            raise ValueError(f"doc_tokens wider than the corpus "
                             f"({tokens.shape[1]} > {width})")
        if tokens.shape[1] < width:
            tokens = np.pad(tokens, ((0, 0), (0, width - tokens.shape[1])),
                            constant_values=bm25.PAD_ID)
        if self._count + n_new > self.delta_capacity:
            raise DeltaFull(
                f"delta segment full: {self._count}/{self.delta_capacity} "
                f"slots used, {n_new} more requested — compact() first")

        assign, a_scores = (np.asarray(v) for v in cs_mod.doc_scores(
            self.base.cluster_sel, jnp.asarray(emb)))
        if self.selectors is not None:
            pos = self.selectors.position_scores(jnp.asarray(tokens))
        else:
            pos = bm25.score_positions(jnp.asarray(tokens), self._stats)
        k1 = int(self.build_kwargs["k1_terms"])
        t_ids, t_scores = bm25.top_terms(jnp.asarray(tokens), pos, k1)
        t_ids, t_scores = np.asarray(t_ids), np.asarray(t_scores)

        codec_impl = codecs.get(self.base.codec)
        enc = codec_impl.encode(self.base.codec_params, jnp.asarray(emb))
        lo = self._count
        for k, v in enc.items():
            self._delta_planes[k][lo:lo + n_new] = np.asarray(v)
        self._delta_emb[lo:lo + n_new] = emb
        self._delta_tokens[lo:lo + n_new] = tokens
        self._delta_assign[lo:lo + n_new] = assign
        if self.filtered:
            self._delta_ns[lo:lo + n_new] = ns

        ids = self.n_base + lo + np.arange(n_new)
        for i in range(n_new):
            gid = int(ids[i])
            if not _insert_posting(self._dc_entries, self._dc_scores,
                                   self._dc_lengths, int(assign[i]), gid,
                                   float(a_scores[i])):
                self.dropped_postings += 1
            for j in range(k1):
                term = int(t_ids[i, j])
                if term < 0:
                    continue
                if not _insert_posting(self._dt_entries, self._dt_scores,
                                       self._dt_lengths, term, gid,
                                       float(t_scores[i, j])):
                    self.dropped_postings += 1
        self._count += n_new
        self._cache = None
        self._epoch += 1
        return ids

    def delete_docs(self, doc_ids) -> None:
        """Tombstone documents by global id (base or delta; idempotent).
        Slots are reclaimed only by ``compact()``."""
        ids = np.asarray(doc_ids).reshape(-1)
        if ids.size and (ids.min() < 0 or ids.max() >= self.n_docs):
            raise ValueError(
                f"doc id out of range [0, {self.n_docs}): "
                f"{ids[(ids < 0) | (ids >= self.n_docs)][:8]}")
        self._tomb[ids] = True
        self._cache = None
        self._epoch += 1

    # --- search ----------------------------------------------------------
    def delta_segment(self) -> DeltaSegment:
        self._materialize()
        return self._cache[0]

    def _materialize(self) -> None:
        if self._cache is None:
            delta = DeltaSegment(
                cluster_lists=PaddedLists(jnp.asarray(self._dc_entries),
                                          jnp.asarray(self._dc_lengths)),
                term_lists=PaddedLists(jnp.asarray(self._dt_entries),
                                       jnp.asarray(self._dt_lengths)),
                doc_planes={k: jnp.asarray(v)
                            for k, v in self._delta_planes.items()},
                doc_assign=jnp.asarray(self._delta_assign),
                doc_ns=(None if self._delta_ns is None
                        else jnp.asarray(self._delta_ns)),
                # the eviction score plane IS the impact plane: -inf at
                # empty slots → 0.0, matching build_scored's pad fill
                sparse_weights=(
                    None if self.base.sparse_weights is None
                    else jnp.where(
                        jnp.asarray(self._dt_entries) == PAD_DOC, 0.0,
                        jnp.asarray(self._dt_scores))))
            self._cache = (delta, jnp.asarray(self._tomb))

    def search(self, query_embeddings, query_tokens, *, kc: int, k2: int,
               top_r: int, use_kernel: bool = False,
               filter=None,
               fusion: Optional[qexec.FusionSpec] = None
               ) -> hi.SearchResult:
        self._materialize()
        delta, tomb = self._cache
        return search(self.base, delta, tomb,
                      jnp.asarray(query_embeddings),
                      jnp.asarray(query_tokens),
                      kc=kc, k2=k2, top_r=top_r, use_kernel=use_kernel,
                      filter=filter, fusion=fusion)

    # --- compaction ------------------------------------------------------
    def survivors(self) -> np.ndarray:
        """Old global ids of the live docs, in the (arrival) order the
        compacted index renumbers them: new id i ↔ old id survivors[i]."""
        return np.flatnonzero(~self._tomb[:self.n_docs])

    def surviving_corpus(self) -> tuple[np.ndarray, np.ndarray]:
        emb = np.concatenate([self._corpus_emb,
                              self._delta_emb[:self._count]])
        tokens = np.concatenate([self._corpus_tokens,
                                 self._delta_tokens[:self._count]])
        live = self.survivors()
        return emb[live], tokens[live]

    def surviving_namespaces(self) -> Optional[np.ndarray]:
        """Namespace ids of the survivors (None on unfiltered indexes)
        — what ``compact()`` re-indexes them under."""
        if not self.filtered:
            return None
        ns = np.concatenate([self._corpus_ns,
                             self._delta_ns[:self._count]])
        return ns[self.survivors()]

    def compact(self, key: Optional[Array] = None) -> "MutableHybridIndex":
        """Fold delta + tombstones into a fresh base with an empty delta.

        Deliberately *is* a from-scratch build over the surviving corpus
        (KMeans, BM25 statistics, codec training and all), with the
        original build key unless overridden — which is what makes the
        equivalence contract exact rather than approximate: the
        compacted index is bit-identical to ``hi.build`` on the
        survivors.  Surviving docs are renumbered contiguously (their
        namespaces travel with them); use :meth:`survivors` for the
        old→new id correspondence.
        """
        emb, tokens = self.surviving_corpus()
        if emb.shape[0] == 0:
            raise ValueError("cannot compact an index with zero live docs")
        out = type(self).create(
            self.key if key is None else key, emb, tokens, self.vocab_size,
            delta_capacity=self.delta_capacity,
            delta_cluster_capacity=self.delta_cluster_capacity,
            delta_term_capacity=self.delta_term_capacity,
            doc_namespaces=self.surviving_namespaces(),
            selectors=self.selectors,
            **self.build_kwargs)
        # compaction renumbers survivors, so epoch-keyed caches must not
        # serve pre-compaction entries against the new index
        out._epoch = self._epoch + 1
        return out

    # --- cost accounting (DESIGN.md §2 latency proxy) --------------------
    def families(self) -> list:
        """(cluster, term) list capacities per gather source — the input
        to the shared cost model (repro.core.exec.cost)."""
        return [(self.base.cluster_lists.capacity,
                 self.base.term_lists.capacity),
                (self.delta_cluster_capacity, self.delta_term_capacity)]

    def candidate_budget(self, kc: int, k2: int) -> int:
        return qexec.candidate_budget(kc, k2, self.families())

    def candidate_cost(self, kc: int, k2: int, top_r: int) -> int:
        return qexec.candidate_cost(self.base.codec, kc, k2, top_r,
                                    self.families())

    # --- persistence (driven by repro.checkpoint) ------------------------
    def state_tree(self) -> dict:
        """The checkpointable pytree: base index + every piece of delta
        and tombstone state (including the retained corpus, the
        namespace planes when filtered, and the list score planes that
        drive overflow eviction, so restored indexes mutate identically
        to never-saved ones)."""
        delta = {
            "cluster_entries": self._dc_entries,
            "cluster_scores": self._dc_scores,
            "cluster_lengths": self._dc_lengths,
            "term_entries": self._dt_entries,
            "term_scores": self._dt_scores,
            "term_lengths": self._dt_lengths,
            "planes": self._delta_planes,
            "assign": self._delta_assign,
            "emb": self._delta_emb,
            "tokens": self._delta_tokens,
        }
        corpus = {"emb": self._corpus_emb, "tokens": self._corpus_tokens}
        if self.filtered:
            delta["ns"] = self._delta_ns
            corpus["ns"] = self._corpus_ns
        return {
            "base": self.base,
            "delta": delta,
            "tombstones": self._tomb,
            "corpus": corpus,
            "key": jax.random.key_data(self.key),
        }

    def state_extra(self) -> dict:
        """JSON-able metadata stored next to :meth:`state_tree`."""
        return {"epoch": self._epoch,
                "delta_count": self._count,
                "delta_capacity": self.delta_capacity,
                "delta_cluster_capacity": self.delta_cluster_capacity,
                "delta_term_capacity": self.delta_term_capacity,
                "vocab_size": self.vocab_size,
                "build_kwargs": self.build_kwargs,
                "filtered": self.filtered,
                "sup_selectors": self.selectors is not None,
                "dropped_postings": self.dropped_postings}

    @classmethod
    def from_state(cls, tree: dict, extra: dict,
                   selectors=None) -> "MutableHybridIndex":
        """Rebuild a mutable index from a restored :meth:`state_tree`
        (leaves may be jnp arrays) + its :meth:`state_extra`.

        Supervised selector *parameters* are not part of the state tree
        (they belong to the training checkpoint, not the index): a
        checkpoint written from a supervised index must be restored with
        the same ``selectors=`` object, or add/compact semantics would
        silently fall back to BM25.
        """
        m = extra["mutable"] if "mutable" in extra else extra
        if m.get("sup_selectors") and selectors is None:
            raise ValueError(
                "checkpoint was written from a supervised index; restore "
                "needs the matching selectors= (e.g. a `like` index that "
                "carries .selectors)")
        corpus_ns = tree["corpus"].get("ns")
        out = cls(tree["base"], vocab_size=int(m["vocab_size"]),
                  key=jax.random.wrap_key_data(jnp.asarray(tree["key"])),
                  build_kwargs=dict(m["build_kwargs"]),
                  delta_capacity=int(m["delta_capacity"]),
                  delta_cluster_capacity=int(m["delta_cluster_capacity"]),
                  delta_term_capacity=int(m["delta_term_capacity"]),
                  corpus_emb=np.asarray(tree["corpus"]["emb"]),
                  corpus_tokens=np.asarray(tree["corpus"]["tokens"]),
                  corpus_ns=(None if corpus_ns is None
                             else np.asarray(corpus_ns)),
                  selectors=selectors)
        d = tree["delta"]
        # np.array (not asarray): restored leaves may be jnp arrays whose
        # numpy views are read-only, and all of this state is mutated
        out._dc_entries = np.array(d["cluster_entries"], np.int32)
        out._dc_scores = np.array(d["cluster_scores"], np.float32)
        out._dc_lengths = np.array(d["cluster_lengths"], np.int32)
        out._dt_entries = np.array(d["term_entries"], np.int32)
        out._dt_scores = np.array(d["term_scores"], np.float32)
        out._dt_lengths = np.array(d["term_lengths"], np.int32)
        out._delta_planes = {k: np.array(v) for k, v in d["planes"].items()}
        out._delta_assign = np.array(d["assign"], np.int32)
        if "ns" in d:
            out._delta_ns = np.array(d["ns"], np.int32)
        out._delta_emb = np.array(d["emb"], np.float32)
        out._delta_tokens = np.array(d["tokens"], np.int32)
        out._tomb = np.array(tree["tombstones"], bool)
        out._count = int(m["delta_count"])
        out.dropped_postings = int(m.get("dropped_postings", 0))
        # epoch travels with the state: a restored index must keep
        # invalidating epoch-keyed caches where the saved one left off
        out._epoch = int(m.get("epoch", 0))
        out._cache = None
        return out


# --------------------------------------------------------------------------
# document-sharded mutable search (DESIGN.md §6 + §8 + §9)
# --------------------------------------------------------------------------

def make_mutable_search_step(mesh, axis_name: str, codec: str, n_base: int,
                             per: int, dper: int, kc: int, k2: int,
                             top_r: int, use_kernel: bool = False,
                             batch_axis: Optional[str] = None,
                             filtered: bool = False,
                             fusion: Optional[qexec.FusionSpec] = None):
    """shard_map'd base∪delta search + merge for one static config.

    Shard ``s`` owns base docs [s·per, (s+1)·per) *and* delta slots
    [s·dper, (s+1)·dper) (global ids ``n_base + slot``).  The body is
    the §9 stage chain over the per-shard (base, delta) source pair
    under a :class:`~repro.core.exec.ShardEnv` — the same engine as
    every other variant, so results stay bit-identical.  With
    ``filtered=True`` the step takes a fifth argument, the replicated
    (B, W) namespace bitmap, and ``planes`` must carry ``base_ns`` /
    ``delta_ns``.  ``batch_axis`` optionally partitions the query batch
    (and the bitmap) over a second mesh axis — the 2-D (data, model)
    serving layout of DESIGN.md §12, same semantics as
    :func:`repro.core.sharded_index.make_search_step`.
    """
    from jax.sharding import PartitionSpec as P

    codec_impl = codecs.get(codec)
    n_shards = mesh.shape[axis_name]

    def body(shard, rep, qe, qt, ns_filter=None):
        shard = jax.tree.map(lambda x: x[0], shard)
        s = jax.lax.axis_index(axis_name)
        b_lo, d_lo = s * per, s * dper
        sources = [
            qexec.Source(
                cluster_lists=PaddedLists(shard["base_cluster_entries"],
                                          shard["base_cluster_lengths"]),
                term_lists=PaddedLists(shard["base_term_entries"],
                                       shard["base_term_lengths"]),
                doc_planes=shard["base_codec"],
                size=per,
                offset=b_lo,
                family_hi=n_base,
                tombstones=shard["tomb_base"],
                doc_ns=shard.get("base_ns"),
                sparse_weights=shard.get("base_sparse_weights")),
            qexec.Source(
                cluster_lists=PaddedLists(shard["delta_cluster_entries"],
                                          shard["delta_cluster_lengths"]),
                term_lists=PaddedLists(shard["delta_term_entries"],
                                       shard["delta_term_lengths"]),
                doc_planes=shard["delta_codec"],
                size=dper,
                offset=n_base + d_lo,
                family_lo=n_base,
                family_hi=n_base + n_shards * dper,
                tombstones=shard["tomb_delta"],
                doc_ns=shard.get("delta_ns"),
                sparse_weights=shard.get("delta_sparse_weights")),
        ]
        res = qexec.execute(
            codec_impl, rep["codec"],
            cs_mod.ClusterSelector(embeddings=rep["cluster_emb"]),
            ts_mod.TermSelector(avg_scores=rep["term_avg"]),
            sources, qe, qt,
            kc=kc, k2=k2, top_r=top_r, use_kernel=use_kernel,
            ns_filter=ns_filter, shard=qexec.ShardEnv(axis_name),
            fusion=fusion)
        return res.doc_ids, res.scores, res.n_candidates

    def specs_like(tree, leading):
        return jax.tree.map(
            lambda x: P(leading, *(None,) * (x.ndim - 1)) if leading
            else P(*(None,) * x.ndim), tree)

    qspec = P(batch_axis, None)

    def run(planes, rep, qe, qt, ns_filter=None):
        in_specs = [specs_like(planes, axis_name), specs_like(rep, None),
                    qspec, qspec]
        args = [planes, rep, qe, qt]
        if filtered:
            in_specs.append(qspec)
            args.append(ns_filter)
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(qspec, qspec, P(batch_axis)),
            check_vma=False)  # outputs replicated by construction (§6 merge)
        return mapped(*args)

    return run


@functools.lru_cache(maxsize=32)
def _compiled_mutable_search(mesh, axis_name, codec, n_base, per, dper,
                             kc, k2, top_r, use_kernel, filtered,
                             batch_axis=None, fusion=None):
    return jax.jit(make_mutable_search_step(
        mesh, axis_name, codec, n_base, per, dper, kc, k2, top_r,
        use_kernel, batch_axis=batch_axis, filtered=filtered,
        fusion=fusion))


class ShardedMutableIndex:
    """Mutable HI² over the document-sharded layout of DESIGN.md §6.

    Wraps a :class:`MutableHybridIndex` (the host-side source of truth)
    and keeps a device-placed sharded view: the immutable base is
    partitioned once at construction; delta planes, namespace planes and
    tombstones are re-split after each mutation, which routes every
    added doc's postings and codec rows to the shard owning its global
    id.  Search is bit-identical to the single-device mutable search
    (asserted for every registered codec by ``tests/test_segments.py``
    and, with filters, ``tests/test_exec.py``).
    """

    def __init__(self, mut: MutableHybridIndex, n_shards: int, mesh=None,
                 axis_name: str = shi.SHARD_AXIS,
                 data_axis: Optional[str] = None):
        self.mut = mut
        self.n_shards = int(n_shards)
        self.axis_name = axis_name
        self.data_axis = data_axis
        if data_axis is not None and mesh is None:
            raise ValueError("data_axis= needs the 2-D mesh passed in "
                             "(launch.mesh.make_serving_mesh)")
        self.mesh = mesh if mesh is not None else shi.make_shard_mesh(
            n_shards, axis_name)
        sbase = shi.partition(mut.base, n_shards)
        self._sbase = shi.device_put(sbase, self.mesh, axis_name)
        self.per = sbase.docs_per_shard
        self.dper = -(-mut.delta_capacity // n_shards)
        self._delta_state: Optional[dict] = None

    # --- mutation: delegate to the host index, re-split the delta --------
    def add_docs(self, doc_emb, doc_tokens, namespaces=None) -> np.ndarray:
        ids = self.mut.add_docs(doc_emb, doc_tokens, namespaces=namespaces)
        self._delta_state = None
        return ids

    def delete_docs(self, doc_ids) -> None:
        self.mut.delete_docs(doc_ids)
        self._delta_state = None

    def compact(self, key: Optional[Array] = None) -> "ShardedMutableIndex":
        return type(self)(self.mut.compact(key), self.n_shards,
                          mesh=self.mesh, axis_name=self.axis_name,
                          data_axis=self.data_axis)

    @property
    def epoch(self) -> int:
        """The wrapped host index's mutation counter (DESIGN.md §10)."""
        return self.mut.epoch

    def owning_shard(self, doc_ids) -> np.ndarray:
        """Which shard serves each global doc id (base range split by
        ``per``, delta slots split by ``dper``)."""
        ids = np.asarray(doc_ids)
        n_base = self.mut.n_base
        return np.where(ids < n_base, ids // self.per,
                        (ids - n_base) // self.dper)

    # --- device state ----------------------------------------------------
    def _split_delta(self) -> dict:
        mut, n_base = self.mut, self.mut.n_base
        s, dper = self.n_shards, self.dper
        dc_e, dc_l = shi._split_lists(mut._dc_entries, s, dper, base=n_base)
        dt_w = None
        if mut.base.sparse_weights is None:
            dt_e, dt_l = shi._split_lists(mut._dt_entries, s, dper,
                                          base=n_base)
        else:
            dw = np.where(mut._dt_entries == PAD_DOC, 0.0,
                          mut._dt_scores).astype(np.float32)
            dt_e, dt_l, dt_w = shi._split_lists(mut._dt_entries, s, dper,
                                                base=n_base, weights=dw)
        tomb = mut._tomb
        state = {
            "delta_cluster_entries": jnp.asarray(dc_e),
            "delta_cluster_lengths": jnp.asarray(dc_l),
            "delta_term_entries": jnp.asarray(dt_e),
            "delta_term_lengths": jnp.asarray(dt_l),
            "delta_codec": {
                k: jnp.asarray(shi._split_docs(v, s, dper))
                for k, v in mut._delta_planes.items()},
            "tomb_base": jnp.asarray(
                shi._split_docs(tomb[:n_base], s, self.per)),
            "tomb_delta": jnp.asarray(
                shi._split_docs(tomb[n_base:], s, dper)),
        }
        if dt_w is not None:
            state["delta_sparse_weights"] = jnp.asarray(dt_w)
        if mut.filtered:
            state["delta_ns"] = jnp.asarray(
                shi._split_docs(mut._delta_ns, s, dper))
        return state

    def _planes(self) -> dict:
        if self._delta_state is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            def put(x):
                return jax.device_put(x, NamedSharding(
                    self.mesh,
                    P(self.axis_name, *(None,) * (x.ndim - 1))))

            self._delta_state = jax.tree.map(put, self._split_delta())
        sb = self._sbase
        planes = {
            "base_cluster_entries": sb.cluster_entries,
            "base_cluster_lengths": sb.cluster_lengths,
            "base_term_entries": sb.term_entries,
            "base_term_lengths": sb.term_lengths,
            "base_codec": sb.doc_planes,
            **self._delta_state,
        }
        if sb.doc_ns is not None:
            planes["base_ns"] = sb.doc_ns
        if sb.sparse_weights is not None:
            planes["base_sparse_weights"] = sb.sparse_weights
        return planes

    def search(self, query_embeddings, query_tokens, *, kc: int, k2: int,
               top_r: int, use_kernel: bool = False,
               filter=None,
               fusion: Optional[qexec.FusionSpec] = None
               ) -> hi.SearchResult:
        if filter is not None and not self.mut.filtered:
            raise ValueError(
                "search(filter=...) needs an index built with "
                "doc_namespaces=")
        rep = {"cluster_emb": self._sbase.cluster_sel.embeddings,
               "term_avg": self._sbase.term_sel.avg_scores,
               "codec": self._sbase.codec_params}
        if self.data_axis is not None:
            d = self.mesh.shape[self.data_axis]
            if np.shape(query_embeddings)[0] % d:
                raise ValueError(
                    f"batch {np.shape(query_embeddings)[0]} does not "
                    f"divide over {d} data-axis slices")
        fn = _compiled_mutable_search(
            self.mesh, self.axis_name, self.mut.base.codec, self.mut.n_base,
            self.per, self.dper, kc, k2, top_r, use_kernel,
            filter is not None, self.data_axis, fusion)
        args = [self._planes(), rep, jnp.asarray(query_embeddings),
                jnp.asarray(query_tokens)]
        if filter is not None:
            args.append(jnp.asarray(filter, jnp.uint32))
        ids, scores, n_cand = fn(*args)
        return hi.SearchResult(doc_ids=ids, scores=scores,
                               n_candidates=n_cand)
