"""Exact top-k inner-product search over the corpus: the oracle of
``recall_at_100``.

Exact means: the k documents of highest ⟨q, x⟩ computed in float32 at
full precision (ties to the lower id).  Scanning 2^20 × 768 documents
at full precision costs six MXU passes per product, so the scan runs in
one bfloat16 pass and only a shortlist is scored at full precision.
The shortlist is provably enough: a bfloat16 product of two rounded
inputs lies within (2u + u²)·|q_i x_i| of the exact one (u = 2⁻⁸), and
float32 accumulation adds at most h·2⁻²⁴·Σ|q_i x_i|, so every score of
the scan is within ``eps = 0.0081·|q|·max|x|`` of the exact score.  A
document left off the shortlist scored at most ``T`` in the scan (the
highest score cut from a block or from the merge), so its exact score is
at most ``T + eps``; where the k-th exact score of the shortlist is
above that, the shortlist holds the exact top k.  Queries for which the
proof fails are scanned again at full precision.
"""
from __future__ import annotations

import functools

import numpy as np

#: documents per block of the scan; queries per call
BLOCK, QUERIES = 131072, 64
#: kept per block, and after the merge
PER_BLOCK, SHORTLIST = 128, 256
#: (2u + u²) + h·2⁻²⁴ for u = 2⁻⁸ and h ≤ 1024, rounded up
EPS_FACTOR = 0.0081


@functools.lru_cache(maxsize=4)
def _scan(n_docs: int, hidden: int, k: int):
    import jax
    import jax.numpy as jnp

    block = min(BLOCK, n_docs)
    n_blocks = n_docs // block
    per_block = min(PER_BLOCK, block)
    short = min(SHORTLIST, per_block * n_blocks)

    @jax.jit
    def run(q, docs):
        qb = q.astype(jnp.bfloat16)
        tops, ids, cut = [], [], []
        for i in range(n_blocks):
            x = jax.lax.dynamic_slice_in_dim(docs, i * block, block)
            s = jnp.dot(qb, x.astype(jnp.bfloat16).T,
                        preferred_element_type=jnp.float32)
            ts, ti = jax.lax.top_k(s, per_block)
            tops.append(ts)
            ids.append(ti + i * block)
            cut.append(ts[:, -1])
        tops = jnp.concatenate(tops, 1)
        ids = jnp.concatenate(ids, 1)
        ms, mi = jax.lax.top_k(tops, short)
        sid = jnp.take_along_axis(ids, mi, 1)
        # the highest scan score of any document not on the shortlist
        cut_block = jnp.max(jnp.stack(cut, 1), 1) if block < n_docs \
            else jnp.full((q.shape[0],), -jnp.inf)
        thresh = jnp.maximum(cut_block, ms[:, -1]) if short < n_docs \
            else jnp.full((q.shape[0],), -jnp.inf)
        exact = jnp.einsum("bh,bkh->bk", q, docs[sid],
                           precision=jax.lax.Precision.HIGHEST)
        return sid, exact, thresh

    @jax.jit
    def full(q, docs):
        return jnp.matmul(q, docs.T, precision=jax.lax.Precision.HIGHEST)

    return run, full


def _top(scores: np.ndarray, ids: np.ndarray, k: int) -> np.ndarray:
    order = np.lexsort((ids, -scores))[:k]
    return ids[order]


def exact_topk(queries: np.ndarray, docs, k: int) -> tuple:
    """(len(queries), k) exact ids, and how many queries needed the full
    rescan.  ``docs`` is the (n_docs, h) float32 corpus on the device."""
    import jax.numpy as jnp

    n_docs, hidden = docs.shape
    run, full = _scan(n_docs, hidden, k)
    max_norm = float(jnp.max(jnp.linalg.norm(docs, axis=1)))
    out = np.zeros((len(queries), k), np.int64)
    rescans = 0
    for lo in range(0, len(queries), QUERIES):
        q = np.asarray(queries[lo:lo + QUERIES], np.float32)
        n = len(q)
        qp = np.zeros((QUERIES, hidden), np.float32)
        qp[:n] = q
        sid, exact, thresh = (np.asarray(a) for a in run(jnp.asarray(qp),
                                                         docs))
        for i in range(n):
            ids = _top(exact[i], sid[i], k)
            kth = np.sort(exact[i])[::-1][k - 1]
            eps = EPS_FACTOR * float(np.linalg.norm(q[i])) * max_norm
            if kth > thresh[i] + eps:
                out[lo + i] = ids
                continue
            rescans += 1
            s = np.asarray(full(jnp.asarray(qp[i:i + 1]), docs))[0]
            out[lo + i] = _top(s, np.arange(n_docs), k)
    return out, rescans
