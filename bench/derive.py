"""The plain reference's own index planes, derived from the benchmark's
corpus.

Only the trained parameters come from the program: the cluster centres
(k-means) and, for OPQ, the rotation and the codewords.  Every plane
that follows from the corpus and those parameters is built here again,
by the definitions the configuration states:

  cluster lists  each document in the list of the centre of highest
                 ⟨x, c⟩, each list cut at its capacity by that score
                 (ties to the lower document id);
  term lists     BM25 (α = 0.82, β = 0.68) over the corpus tokens, each
                 document in the lists of its K₁ᵀ highest-scoring
                 distinct terms (ties to the earlier position), each
                 list cut at its capacity by score (ties to the lower
                 id); the corpus-average score of each term;
  OPQ codes      per fragment of x·R the codeword of highest
                 ⟨x_j, c⟩ − ‖c‖²/2;
  sq8 range      the per-dimension minimum and (max − min)/255; the
                 codes themselves are made per query by the reference.

Where float32, as the program computes, and this computation may decide
differently (two scores within a relative :data:`RTOL`, or
:data:`BM25_RTOL` for BM25 scores), both outcomes are kept: a document
whose membership is so decided is a *maybe* member of its lists, and a
code so decided carries its alternatives.  The reference then accepts either outcome.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

#: relative width within which float32 may order two scores differently
RTOL = 1e-5
#: the same for BM25 scores: a TPU's float32 log is off by up to 2.6e-4
#: of its value, and its BM25 scores over 1 by up to 7.1e-5 (TPU v5e)
BM25_RTOL = 1e-3
#: rows per block of the device passes
BLOCK = 8192
SURE, MAYBE, OUT = 2, 1, 0
BM25_ALPHA, BM25_BETA = 0.82, 0.68


class Lists(NamedTuple):
    """Inverted lists as two CSR planes: the documents surely in each
    list, and those that may be."""
    sure_ptr: np.ndarray
    sure: np.ndarray
    maybe_ptr: np.ndarray
    maybe: np.ndarray

    def members(self, lists) -> tuple:
        """(sure ids, maybe ids) of the union of ``lists``, sorted; a
        document sure in one list is sure."""
        def gather(ptr, ids):
            parts = [ids[ptr[v]:ptr[v + 1]] for v in lists]
            return np.unique(np.concatenate(parts)) if parts else \
                np.zeros(0, np.int64)
        sure = gather(self.sure_ptr, self.sure)
        maybe = gather(self.maybe_ptr, self.maybe)
        return sure, np.setdiff1d(maybe, sure, assume_unique=True)

    @property
    def n_maybe(self) -> int:
        return len(self.maybe)


class Codes(NamedTuple):
    """A code plane and, for the few (document, position) pairs float32
    may decide otherwise, every code that it may pick there."""
    best: np.ndarray        # (n_docs, w) uint8
    alt_doc: np.ndarray     # (a,) sorted
    alt_pos: np.ndarray     # (a,)
    alt_code: np.ndarray    # (a,) one alternative each, besides ``best``


class Planes(NamedTuple):
    clusters: Lists
    terms: Lists
    avg_scores: np.ndarray       # (V,) f64
    codes: Optional[Codes]       # OPQ
    lo: Optional[np.ndarray]     # sq8, (h,) f32
    scale: Optional[np.ndarray]  # sq8, (h,) f32


# --------------------------------------------------------------------------
# lists and their capacity cut
# --------------------------------------------------------------------------

def _cut(s: np.ndarray, maybe: np.ndarray, cap: int,
         rtol: float = RTOL) -> np.ndarray:
    """Status of each posting of one overflowing list, its postings
    sorted by (score desc, doc asc).  Exactly equal scores are ordered
    by document id both here and in the program; scores within the
    tolerance may be ordered either way, and a maybe member may be
    absent."""
    n = len(s)
    idx = np.arange(n)
    tol = rtol * max(float(np.abs(s).max()), 1e-30)
    neg = -s
    grp_start = np.searchsorted(neg, neg, side="left")
    grp_end = np.searchsorted(neg, neg, side="right")
    sure = ~maybe
    sure_cum = np.concatenate([[0], np.cumsum(sure)])
    # surely ahead: sure postings above the band, and sure ones equal
    # to it with a lower id
    n_gt = np.searchsorted(neg, neg - tol, side="left")
    before_min = sure_cum[n_gt] + sure_cum[idx] - sure_cum[grp_start]
    # possibly ahead: every posting down to the band's lower edge, but
    # for the equal ones with a higher id
    n_ge = np.searchsorted(neg, neg + tol, side="right")
    before_max = n_ge - 1 - (grp_end - 1 - idx)
    status = np.full(n, MAYBE, np.int8)
    status[sure & (before_max < cap)] = SURE
    status[before_min >= cap] = OUT
    return status


def bucket(doc, lst, score, maybe, n_lists: int, capacity: int,
           rtol: float = RTOL) -> Lists:
    """Postings (doc, list, score, maybe) into lists cut at
    ``capacity`` by score, ties to the lower document id, scores within
    a relative ``rtol`` either way."""
    order = np.lexsort((doc, -score, lst))
    doc, lst, score, maybe = doc[order], lst[order], score[order], \
        maybe[order]
    counts = np.bincount(lst, minlength=n_lists)
    starts = np.zeros(n_lists + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    status = np.where(maybe, MAYBE, SURE).astype(np.int8)
    for v in np.flatnonzero(counts > capacity):
        a, b = starts[v], starts[v + 1]
        status[a:b] = _cut(score[a:b], maybe[a:b], capacity, rtol)

    def csr(keep):
        ptr = np.zeros(n_lists + 1, np.int64)
        np.cumsum(np.bincount(lst[keep], minlength=n_lists), out=ptr[1:])
        return ptr, doc[keep].astype(np.int64)

    return Lists(*csr(status == SURE), *csr(status == MAYBE))


# --------------------------------------------------------------------------
# the cluster side
# --------------------------------------------------------------------------

def _blocked(fn, x, block: int = BLOCK):
    """``fn`` over row blocks of the device array ``x`` (the last block
    padded), results on the host."""
    import jax.numpy as jnp

    n = x.shape[0]
    outs = []
    for lo in range(0, n, block):
        xi = x[lo:lo + block]
        if xi.shape[0] < block:
            xi = jnp.pad(xi, ((0, block - xi.shape[0]), (0, 0)))
        outs.append([np.asarray(o) for o in fn(xi)])
    return [np.concatenate(parts)[:n] for parts in zip(*outs)]


@functools.lru_cache(maxsize=None)
def _top3_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def top3(x, c):
        s = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
        return jax.lax.top_k(s, 3)
    return top3


def cluster_lists(doc_emb, centroids: np.ndarray, capacity: int) -> Lists:
    import jax.numpy as jnp

    c = jnp.asarray(centroids, jnp.float32)
    fn = _top3_fn()
    top_s, top_i = _blocked(lambda xi: fn(xi, c), doc_emb)
    top_s = top_s.astype(np.float64)
    n = len(top_s)
    tol = RTOL * max(float(np.abs(top_s[:, 0]).max()), 1e-30)
    near = top_s[:, :1] - top_s <= tol            # (n, 3); column 0 true
    doc = np.repeat(np.arange(n), 3).reshape(n, 3)
    keep = near.copy()
    maybe = np.broadcast_to(near[:, 1:2], near.shape).copy()
    return bucket(doc[keep], top_i[keep].astype(np.int64), top_s[keep],
                  maybe[keep], len(centroids), capacity)


# --------------------------------------------------------------------------
# the term side: BM25
# --------------------------------------------------------------------------

def term_lists(tokens: np.ndarray, vocab: int, k1: int,
               capacity: int) -> tuple:
    """(term Lists, corpus-average score of each term)."""
    tokens = np.asarray(tokens)
    n, width = tokens.shape
    order = np.argsort(tokens, axis=1, kind="stable")
    st = np.take_along_axis(tokens, order, axis=1)
    start = np.ones_like(st, bool)
    start[:, 1:] = st[:, 1:] != st[:, :-1]
    first = np.flatnonzero(start)
    tf = np.diff(np.append(first, n * width)).astype(np.float64)
    doc = first // width
    term = st.ravel()[first]
    pos = order.ravel()[first]
    ok = term >= 0
    doc, term, pos, tf = doc[ok], term[ok], pos[ok], tf[ok]

    doc_len = (tokens >= 0).sum(axis=1).astype(np.float64)
    df = np.bincount(term, minlength=vocab).astype(np.float64)
    idf = np.maximum(np.log((n - df + 0.5) / (df + 0.5) + 1.0), 0.0)
    a, b = BM25_ALPHA, BM25_BETA
    denom = tf + a * (1.0 - b + b * doc_len[doc] / doc_len.mean())
    score = (a + 1.0) * idf[term] * tf / np.maximum(denom, 1e-6)
    avg = np.bincount(term, weights=score, minlength=vocab) / \
        np.maximum(df, 1.0)

    # each document's k1 best distinct terms, ties to the earlier position
    s = np.full((n, width), -np.inf)
    s[doc, pos] = score
    k1 = min(k1, width)
    kth = np.partition(s, width - k1, axis=1)[:, width - k1]
    gt = s > kth[:, None]
    eq = (s == kth[:, None]) & np.isfinite(kth)[:, None]
    n_gt = gt.sum(axis=1)
    member = gt | (eq & (np.cumsum(eq, axis=1) <= (k1 - n_gt)[:, None]))
    maybe = np.zeros_like(member)
    # a row is looked at whole where a score other than the k-th lies
    # within the tolerance of it, among the k1 + 2 best
    j = max(width - k1 - 2, 0)
    top = np.partition(s, j, axis=1)[:, j:]
    tol = BM25_RTOL * np.abs(np.where(np.isfinite(kth), kth, 0.0))[:, None]
    close = np.isfinite(top) & (np.abs(top - kth[:, None]) <= tol)
    for r in np.flatnonzero((close & (top != kth[:, None])).any(axis=1)
                            | close.all(axis=1)):
        finite = np.isfinite(s[r])
        band = finite & (np.abs(s[r] - kth[r]) <= tol[r, 0])
        above = finite & (s[r] > kth[r] + tol[r, 0])
        if len(np.unique(s[r][band])) > 1 and \
                band.sum() > k1 - above.sum():
            member[r] = above | band
            maybe[r] = band
    d, p = np.nonzero(member)
    lists = bucket(d.astype(np.int64), tokens[d, p].astype(np.int64),
                   s[d, p], maybe[d, p], vocab, capacity, BM25_RTOL)
    return lists, avg


# --------------------------------------------------------------------------
# codes
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _opq_fn():
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def encode(x, rot, cw):
        m, _, dsub = cw.shape
        xr = jnp.matmul(x, rot, precision=hi).reshape(x.shape[0], m, dsub)
        s = jnp.einsum("bmd,mkd->bmk", xr, cw, precision=hi) \
            - 0.5 * jnp.sum(cw * cw, axis=-1)
        top_s, top_i = jax.lax.top_k(s, 2)
        scale = (jnp.linalg.norm(xr, axis=-1)
                 * jnp.max(jnp.linalg.norm(cw, axis=-1), axis=-1)
                 + 0.5 * jnp.max(jnp.sum(cw * cw, axis=-1), axis=-1))
        near = top_s[..., 0] - top_s[..., 1] <= RTOL * scale
        return top_i[..., 0].astype(jnp.uint8), near
    return encode


def opq_codes(doc_emb, rotation: np.ndarray, codewords: np.ndarray,
              rows) -> Codes:
    """The OPQ code plane; where the best two codewords of a fragment lie
    within the tolerance, every codeword within it (by float64 over the
    corpus row) is an alternative."""
    import jax.numpy as jnp

    fn = _opq_fn()
    rot = jnp.asarray(rotation, jnp.float32)
    cw = jnp.asarray(codewords, jnp.float32)
    best, near = _blocked(lambda xi: fn(xi, rot, cw), doc_emb)
    d_amb, j_amb = np.nonzero(near)
    alt_doc, alt_pos, alt_code = [], [], []
    if len(d_amb):
        m, k, dsub = codewords.shape
        cw64 = codewords.astype(np.float64)
        c_half = 0.5 * (cw64 ** 2).sum(-1)
        docs = np.unique(d_amb)
        xr = rows(docs).astype(np.float64) @ rotation.astype(np.float64)
        xr = xr.reshape(len(docs), m, dsub)
        at = np.searchsorted(docs, d_amb)
        for d, j, i in zip(d_amb, j_amb, at):
            s = cw64[j] @ xr[i, j] - c_half[j]
            scale = (np.linalg.norm(xr[i, j])
                     * np.linalg.norm(cw64[j], axis=-1).max()
                     + c_half[j].max())
            for c in np.flatnonzero(s >= s.max() - 2 * RTOL * scale):
                if c != best[d, j]:
                    alt_doc.append(d)
                    alt_pos.append(j)
                    alt_code.append(c)
    alt_doc = np.asarray(alt_doc, np.int64)
    order = np.argsort(alt_doc, kind="stable")
    return Codes(best, alt_doc[order],
                 np.asarray(alt_pos, np.int64)[order],
                 np.asarray(alt_code, np.int64)[order])


def sq8_range(doc_emb) -> tuple:
    """(lo, scale): the per-dimension minimum and (max − min)/255, 1
    where a dimension is constant."""
    import jax.numpy as jnp

    lo = np.asarray(jnp.min(doc_emb, axis=0))
    hi = np.asarray(jnp.max(doc_emb, axis=0))
    span = hi - lo
    return lo, np.where(span > 0, span / np.float32(255.0),
                        np.float32(1.0)).astype(np.float32)


def build(cfg: dict, trained, doc_emb, doc_tokens, rows) -> Planes:
    """Every plane the reference needs, from the corpus (device arrays)
    and the trained parameters."""
    clusters = cluster_lists(doc_emb, trained.centroids,
                             cfg["cluster_capacity"])
    terms, avg = term_lists(np.asarray(doc_tokens), cfg["vocab"],
                            cfg["k1_terms"], cfg["term_capacity"])
    codes = lo = scale = None
    if trained.codec == "opq":
        codes = opq_codes(doc_emb, trained.params["rotation"],
                          trained.params["codewords"], rows)
    else:
        lo, scale = sq8_range(doc_emb)
    return Planes(clusters, terms, avg, codes, lo, scale)
