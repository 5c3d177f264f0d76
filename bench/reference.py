"""Plain reference of HI² search, in numpy, for the check of ``correct``.

It imports nothing of the program.  It takes from the program only the
trained parameters (:class:`Trained`: the cluster centres, and for OPQ
the rotation and the codewords); every other plane it builds itself
from the benchmark's corpus (:mod:`derive`).  From those it computes
each stage in float64:

  dispatch   the K^C clusters of highest ⟨q, centre⟩ (ties to the lower
             cluster id) and up to K₂ᵀ of the query's distinct tokens by
             corpus-average score (ties to the earlier position);
  gather     every document of those lists, once;
  score      OPQ: rotate the query, one table of ⟨q_j, codeword⟩ per
             fragment, the sum of the m table entries a document's codes
             pick.  sq8: ⟨q, code·scale + lo⟩, each code
             round((x − lo)/scale) of the corpus row;
  top-R      the R documents of highest score, ties to the lower id;
  refine     (refine codecs) the top mult·R by stage-1 score, re-ranked
             by ⟨q, fp16(x)⟩ over the benchmark's own corpus rows.

Where the program's float32 and this float64 may decide differently
(clusters at the K^C-th within :data:`TIE`; a list member or a code
within the tolerance of :mod:`derive`), every such outcome is kept: each
cluster choice is an :class:`Option`; a maybe member may or may not be
a candidate; a document with alternative codes has a set of stage-1
scores.  The comparison (:func:`check.compare`) takes the outcome that
fits the program's answer best, and measures the R-th score against a
lower bound that holds under every outcome.

``precision="bfloat16"`` is the control of the check: the same stages
with every product's inputs rounded to bfloat16 and float32
accumulation, one MXU pass, the step below the configuration's float32.
"""
from __future__ import annotations

import itertools
from typing import Callable, NamedTuple

import numpy as np

from bench.derive import Planes

PAD = -1
#: scores closer than this may order differently in float32 and float64
TIE = 1e-5
#: most combinations of alternative codes enumerated for one document;
#: past it the document's stage-1 scores are taken as an interval
MAX_COMBOS = 4096


class Trained(NamedTuple):
    """What the reference takes from the program: trained parameters."""
    centroids: np.ndarray   # (L, h) f32
    codec: str              # "opq" or "sq8"
    params: dict            # opq: rotation, codewords; sq8: none
    refine_mult: int        # 0 without a refine stage


class Option(NamedTuple):
    """The reference's answer under one choice of clusters.

    ``floor``: a lower bound of the R-th final score under every outcome
    of this choice.  ``final(doc, score)``: the final score of ``doc``
    under the outcome nearest ``score``, or None where ``doc`` cannot
    be in the answer."""
    ids: np.ndarray         # every possible candidate, sorted
    floor: float
    final: Callable


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return np.asarray(x, np.float64)
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _dot(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """a @ b with inputs at ``precision`` (float32 accumulation for
    bfloat16, as one MXU pass accumulates)."""
    return (_round(a, precision) @ _round(b, precision)).astype(np.float64)


def _top(scores: np.ndarray, ids: np.ndarray, k: int) -> np.ndarray:
    """Positions of the k highest scores, ties to the lower id."""
    return np.lexsort((ids, -scores))[:k]


def _kth(values: np.ndarray, k: int) -> float:
    return float(-np.partition(-values, k - 1)[k - 1]) \
        if len(values) >= k else -np.inf


def _choices(scores: np.ndarray, k: int, exact: bool) -> list:
    """The sets of ``k`` ids (positions) that are the top k under some
    order of the scores within :data:`TIE` of the k-th."""
    ids = np.arange(len(scores))
    pos = _top(scores, ids, len(ids))
    if len(ids) <= k or exact:
        return [np.sort(ids[pos[:k]])]
    kth = scores[pos[k - 1]]
    core = ids[scores > kth + TIE]
    band = ids[np.abs(scores - kth) <= TIE]
    return [np.sort(np.concatenate([core, np.asarray(c, ids.dtype)]))
            for c in itertools.combinations(band.tolist(), k - len(core))]


class Stage1(NamedTuple):
    """Stage-1 scores of a set of documents: ``base`` under the best
    codes, and for a document with alternative codes the per-position
    deltas each alternative adds (``deltas[i]``: list of arrays)."""
    base: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    deltas: dict

    def nearest(self, i: int, s: float) -> float:
        if i not in self.deltas:
            return float(self.base[i])
        parts = self.deltas[i]
        if np.prod([len(p) for p in parts]) > MAX_COMBOS:
            return float(np.clip(s, self.lo[i], self.hi[i]))
        sums = np.zeros(1)
        for p in parts:
            sums = (sums[:, None] + p[None, :]).ravel()
        vals = self.base[i] + sums
        return float(vals[np.argmin(np.abs(vals - s))])


class Reference:
    """The reference over the trained parameters, its own planes and
    the corpus rows."""

    def __init__(self, trained: Trained, planes: Planes, cfg: dict,
                 emb_rows: Callable[[np.ndarray], np.ndarray],
                 precision: str = "float64"):
        self.t = trained
        self.p = planes
        self.kc, self.k2, self.r = cfg["kc"], cfg["k2"], cfg["top_r"]
        self.emb_rows = emb_rows
        self.prec = precision
        self.exact = precision != "float64"   # the control answers once
        if trained.codec == "opq":
            self._cw = _round(trained.params["codewords"], precision)
            self._rot = trained.params["rotation"]
            codes = planes.codes
            self._alt_at = np.searchsorted(codes.alt_doc,
                                           np.arange(len(codes.best) + 1))
        elif trained.codec == "sq8":
            self._scale = planes.scale.astype(np.float64)
            self._lo = planes.lo.astype(np.float64)
            self._v_tol = 16 * np.finfo(np.float32).eps
        else:
            raise ValueError(f"no reference for codec {trained.codec!r}")

    # --- stages ------------------------------------------------------------
    def clusters(self, q: np.ndarray) -> list:
        s = _dot(self.t.centroids, q, self.prec)
        return _choices(s, self.kc, self.exact)

    def terms(self, tok: np.ndarray) -> np.ndarray:
        seen, first = set(), []
        for t in tok.tolist():
            if t != PAD and t not in seen:
                seen.add(t)
                first.append(t)
        first = np.asarray(first, np.int64)
        if len(first) == 0:
            return first
        # with K2 at least the query's length (both configurations) every
        # distinct token is dispatched, and the order below decides nothing
        sbar = self.p.avg_scores[first]
        k_eff = min(self.k2, len(tok))
        return first[_top(sbar, np.arange(len(first)), k_eff)]

    def stage1(self, q: np.ndarray, ids: np.ndarray) -> Stage1:
        deltas: dict = {}
        if self.t.codec == "opq":
            qr = _dot(q[None], self._rot, self.prec)[0]
            m, _, dsub = self._cw.shape
            frag = _round(qr.reshape(m, dsub), self.prec)
            lut = np.einsum("md,mkd->mk", frag, self._cw).astype(np.float64)
            codes = self.p.codes
            best = codes.best[ids].astype(np.int64)
            base = lut[np.arange(m)[None, :], best].sum(axis=1)
            a, b = self._alt_at[ids], self._alt_at[ids + 1]
            for i in np.flatnonzero(b > a):
                by_pos: dict = {}
                for j, c in zip(codes.alt_pos[a[i]:b[i]],
                                codes.alt_code[a[i]:b[i]]):
                    by_pos.setdefault(int(j), [0.0]).append(
                        lut[j, c] - lut[j, best[i, j]])
                deltas[int(i)] = [np.asarray(v) for v in by_pos.values()]
        else:
            x = np.asarray(self.emb_rows(ids), np.float64)
            v = (x - self._lo) / self._scale
            code = np.clip(np.rint(v), 0, 255)
            deq = code * self._scale + self._lo
            base = _dot(deq, q, self.prec)
            frac = v - np.floor(v)
            near = (np.abs(frac - 0.5) <= self._v_tol * np.maximum(v, 1.0)) \
                & (v > 0) & (v < 255)
            step = q * self._scale
            for i in np.flatnonzero(near.any(axis=1)):
                dims = np.flatnonzero(near[i])
                other = np.where(code[i, dims] == np.floor(v[i, dims]),
                                 code[i, dims] + 1, code[i, dims] - 1)
                deltas[int(i)] = [np.asarray([0.0, d]) for d in
                                  step[dims] * (other - code[i, dims])]
        lo, hi = base.copy(), base.copy()
        for i, parts in deltas.items():
            lo[i] += sum(p.min() for p in parts)
            hi[i] += sum(p.max() for p in parts)
        return Stage1(base, lo, hi, deltas)

    def exact_scores(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        rows = np.asarray(self.emb_rows(ids), np.float32).astype(np.float16)
        return _dot(rows.astype(np.float32), q, self.prec)

    # --- one query -----------------------------------------------------------
    def options(self, q: np.ndarray, tok: np.ndarray) -> list:
        """One :class:`Option` per choice of clusters."""
        q = np.asarray(q, np.float64)
        terms = self.terms(np.asarray(tok))
        choices = [self.p.clusters.members(c) for c in self.clusters(q)]
        t_sure, t_maybe = self.p.terms.members(terms)
        sets = []
        for c_sure, c_maybe in choices:
            sure = np.union1d(c_sure, t_sure)
            maybe = np.setdiff1d(np.union1d(c_maybe, t_maybe), sure)
            sets.append((sure, maybe))
        every = np.unique(np.concatenate([np.concatenate(s) for s in sets]))
        s1 = self.stage1(q, every)
        return [self._option(q, every, s1, sure, maybe)
                for sure, maybe in sets]

    def _option(self, q, every, s1: Stage1, sure, maybe) -> Option:
        ids = np.union1d(sure, maybe)
        at = np.searchsorted(every, ids)
        is_sure = np.isin(ids, sure, assume_unique=True)
        if not self.t.refine_mult:
            floor = _kth(s1.lo[at][is_sure], self.r)

            def final(d, s):
                i = np.searchsorted(ids, d)
                if i >= len(ids) or ids[i] != d:
                    return None
                return s1.nearest(int(at[i]), s)
            return Option(ids, floor, final)

        # the frontier: the top mult·R by stage-1 score
        k = self.t.refine_mult * self.r
        lo, hi = s1.lo[at], s1.hi[at]
        hi_sorted = np.sort(hi)
        lo_sure_sorted = np.sort(lo[is_sure])
        n_above_max = len(hi) - np.searchsorted(hi_sorted, lo - TIE) - 1
        surely_in = is_sure & (n_above_max < k)
        n_above_min = len(lo_sure_sorted) - np.searchsorted(
            lo_sure_sorted, hi + TIE, side="right")
        possibly = n_above_min < k
        front = ids[possibly]
        exact = dict(zip(front.tolist(),
                         self.exact_scores(q, front).tolist()))
        floor = _kth(np.asarray([exact[d] for d in ids[surely_in]]), self.r)

        def final(d, s):
            return exact.get(int(d))
        return Option(ids, floor, final)

    def control_answer(self, q: np.ndarray, tok: np.ndarray) -> tuple:
        """(ids, scores) of the top R, as this reference computes them
        in the program's place (every possible candidate taken, each
        document's best codes)."""
        q = np.asarray(q, np.float64)
        c_sure, c_maybe = self.p.clusters.members(self.clusters(q)[0])
        t_sure, t_maybe = self.p.terms.members(self.terms(np.asarray(tok)))
        ids = np.unique(np.concatenate([c_sure, c_maybe, t_sure, t_maybe]))
        s = self.stage1(q, ids).base
        if self.t.refine_mult:
            front = ids[_top(s, ids, self.t.refine_mult * self.r)]
            ids, s = front, self.exact_scores(q, front)
        pos = _top(s, ids, self.r)
        return ids[pos], s[pos]

    def n_ambiguous(self) -> dict:
        """How much the reference leaves open, for the record."""
        out = {"maybe_cluster_postings": self.p.clusters.n_maybe,
               "maybe_term_postings": self.p.terms.n_maybe}
        if self.p.codes is not None:
            out["alternative_codes"] = len(self.p.codes.alt_doc)
        return out
