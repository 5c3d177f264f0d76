"""Fixed-capacity padded inverted lists — the TPU-native replacement for
Faiss's variable-length postings (DESIGN.md §2).

An inverted file is stored as two dense planes:

    entries: (n_lists, capacity) int32 doc ids, PAD (-1) beyond length
    lengths: (n_lists,)          int32

Construction happens once, host-side (numpy) — exactly like Faiss's CPU
index build — but every *search-time* operation (dispatch, gather, merge,
dedup) is fixed-shape jitted JAX.  Overflowing lists are truncated by
per-document score, which is the same operation as the paper's static
index pruning (Appendix B) applied at build time; :mod:`repro.core.pruning`
implements the percentile-threshold variant on an already-built index.

Postings are **impact-ordered**: :func:`build` sorts each list by
descending per-document score before the capacity cut, so ``entries``
row v holds term v's highest-impact documents first.  The sparse query
path (DESIGN.md §13) rides on that layout: :func:`build_scored`
additionally materializes the scores as an aligned ``(n_lists,
capacity)`` f32 *impact plane* (0 at pads), which makes BM25 search a
fixed-shape gather + per-document sum over the ≤K₂ᵀ probed term lists
— never an exhaustive (B, V) matmul — using the same list planes the
dense path dispatches over.

At scale the ``entries`` plane is sharded over the mesh ``model`` axis
(row-sharding over lists); see ``repro/distributed/sharding.py``.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Array = jax.Array

PAD_DOC = -1


class PaddedLists(NamedTuple):
    entries: Array   # (n_lists, capacity) i32, PAD_DOC padded
    lengths: Array   # (n_lists,) i32

    @property
    def n_lists(self) -> int:
        return self.entries.shape[0]

    @property
    def capacity(self) -> int:
        return self.entries.shape[1]


def _bucket(doc_ids: np.ndarray, list_ids: np.ndarray,
            scores: Optional[np.ndarray], n_lists: int,
            capacity: Optional[int]
            ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Shared bucketing body of :func:`build` / :func:`build_scored`:
    returns (entries, lengths, weights) numpy planes, weights holding
    each surviving posting's score (0 at pads), aligned with entries."""
    doc_ids = np.asarray(doc_ids).reshape(-1)
    list_ids = np.asarray(list_ids).reshape(-1)
    keep = list_ids >= 0
    doc_ids, list_ids = doc_ids[keep], list_ids[keep]
    if scores is None:
        scores = -np.arange(len(doc_ids), dtype=np.float64)  # FIFO
    else:
        scores = np.asarray(scores, np.float64).reshape(-1)[keep]

    # sort by (list, -score) then cut each list at capacity
    order = np.lexsort((-scores, list_ids))
    doc_ids, list_ids, scores = doc_ids[order], list_ids[order], scores[order]
    counts = np.bincount(list_ids, minlength=n_lists)
    if capacity is None:
        capacity = max(int(counts.max(initial=1)), 1)

    starts = np.zeros(n_lists + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    rank_in_list = np.arange(len(doc_ids)) - starts[list_ids]
    keep2 = rank_in_list < capacity

    entries = np.full((n_lists, capacity), PAD_DOC, np.int32)
    entries[list_ids[keep2], rank_in_list[keep2]] = doc_ids[keep2]
    weights = np.zeros((n_lists, capacity), np.float32)
    weights[list_ids[keep2], rank_in_list[keep2]] = scores[keep2]
    lengths = np.minimum(counts, capacity).astype(np.int32)
    return entries, lengths, weights


def build(doc_ids: np.ndarray, list_ids: np.ndarray, scores: Optional[np.ndarray],
          n_lists: int, capacity: Optional[int] = None) -> PaddedLists:
    """Bucket (doc, list[, score]) assignment triples into padded lists.

    ``doc_ids``/``list_ids``: (n_assignments,). Assignments with negative
    list id (PAD terms) are dropped. If a list overflows ``capacity`` the
    lowest-scoring documents are dropped (score defaults to insertion
    order → FIFO truncation).
    """
    entries, lengths, _ = _bucket(doc_ids, list_ids, scores, n_lists,
                                  capacity)
    return PaddedLists(entries=jnp.asarray(entries), lengths=jnp.asarray(lengths))


def build_scored(doc_ids: np.ndarray, list_ids: np.ndarray,
                 scores: np.ndarray, n_lists: int,
                 capacity: Optional[int] = None
                 ) -> tuple[PaddedLists, Array]:
    """:func:`build` plus the aligned impact plane for sparse search
    (DESIGN.md §13): ``weights[v, j]`` is the per-document score of
    posting ``entries[v, j]`` (0.0 at pads), so a sparse query scores
    candidates by gathering the same rows the dense path gathers and
    summing impacts per document — no second postings structure.

    ``scores`` is required: an impact plane built from the FIFO
    fallback's synthetic insertion-order scores would rank documents by
    arrival, not relevance, silently.
    """
    if scores is None:
        raise ValueError(
            "build_scored needs real per-posting scores; the FIFO "
            "fallback of build() has no meaningful impacts")
    entries, lengths, weights = _bucket(doc_ids, list_ids, scores, n_lists,
                                        capacity)
    return (PaddedLists(entries=jnp.asarray(entries),
                        lengths=jnp.asarray(lengths)),
            jnp.asarray(weights))


@jax.jit
def gather_candidates(lists: PaddedLists, dispatched: Array) -> Array:
    """Fetch the contents of the dispatched lists for a query batch.

    dispatched: (B, K) list ids (PAD=-1 allowed) →
    candidates: (B, K·capacity) doc ids with PAD_DOC where invalid.
    """
    safe = jnp.clip(dispatched, 0, None)
    rows = lists.entries[safe]                                   # (B, K, cap)
    rows = jnp.where((dispatched >= 0)[:, :, None], rows, PAD_DOC)
    return rows.reshape(dispatched.shape[0], -1)


@jax.jit
def dedup_mask(candidates: Array) -> Array:
    """First-occurrence mask over each row — TPU-friendly set semantics.

    Duplicates arise when a document sits in several dispatched lists
    (cluster ∩ term hits).  Two sorts and no gather — O(B·C log C),
    fixed shape, no hashing:

    1. one stable sort of (ids, slot) keyed by id returns the sorted ids
       *and* each one's slot, so the first occurrence in candidate order
       leads its run of equal ids;
    2. a slot is kept if its id differs from its left neighbour's and is
       not PAD;
    3. one sort of ``slot * 2 + keep`` carries that bit back to slot
       order: ``slot`` is a permutation of ``0..C-1``, so the sorted
       keys are ``2i + keep_i`` and ``& 1`` reads slot i's bit.

    Both permuted values ride the sorts as payload: a ``take_along_axis``
    (or a scatter) over C slots lowers on the TPU to a serialised
    per-element gather that costs ~10× the sorts around it.
    """
    b, c = candidates.shape
    if 2 * c > np.iinfo(np.int32).max:
        raise ValueError(f"dedup_mask packs slot*2+bit into int32; C={c}")
    slots = lax.broadcasted_iota(jnp.int32, (b, c), 1)
    sorted_ids, order = lax.sort((candidates, slots), dimension=-1,
                                 num_keys=1, is_stable=True)
    is_dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=-1)
    keep_sorted = (~is_dup) & (sorted_ids != PAD_DOC)
    packed = lax.sort(order * 2 + keep_sorted.astype(jnp.int32), dimension=-1,
                      is_stable=False)    # distinct keys: order-free
    return (packed & 1).astype(bool)


def list_size_histogram(lists: PaddedLists) -> np.ndarray:
    return np.asarray(lists.lengths)
