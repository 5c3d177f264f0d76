"""The comparison that decides ``correct``.

Every answer of the window is held to the result contract: R document
ids, each in range and none twice, with finite scores in descending
order.  A sample of the answers, drawn from the seed once the window has
closed, is compared with the plain reference (:mod:`reference`):

    score_err  the widest gap between a score the program returned and
               the reference's score of that document (a document the
               reference cannot have in the answer reads as infinite);
    rank_gap   the widest amount by which a returned document's
               reference score lies below a lower bound of the
               reference's R-th best.

Where float32 and the reference's float64 may decide differently, the
reference keeps every outcome (:mod:`reference`), and each answer is
measured under the outcome that fits it best.

Each number has its limit in the configuration file (``limits``); no
compile may happen inside the window.
"""
from __future__ import annotations

import numpy as np


def contract_violations(ids: list, scores: list, r: int,
                        n_docs: int) -> int:
    """Answers of the window that break the result contract (missing
    answers are counted by the loop, not here)."""
    bad = 0
    for i, s in zip(ids, scores):
        if i is None:
            continue
        i = np.asarray(i)
        s = np.asarray(s)
        ok = (i.shape == (r,) and s.shape == (r,)
              and bool(((i >= 0) & (i < n_docs)).all())
              and len(np.unique(i)) == r
              and bool(np.isfinite(s).all())
              and bool((np.diff(s) <= 0).all()))
        bad += not ok
    return bad


def compare(ids: np.ndarray, scores: np.ndarray, options: list) -> tuple:
    """(score_err, rank_gap) of one answer under the reference's option
    that fits it best; a returned document that cannot be in the answer
    makes both infinite."""
    best = (np.inf, np.inf)
    for opt in options:
        matched = [opt.final(int(d), float(s)) for d, s in zip(ids, scores)]
        if any(m is None for m in matched):
            continue
        m = np.asarray(matched)
        got = (float(np.max(np.abs(np.asarray(scores, np.float64) - m))),
               max(0.0, opt.floor - float(m.min())))
        if max(got) < max(best):
            best = got
    return best


def sample(n_answers: int, k: int, rng) -> np.ndarray:
    k = min(k, n_answers)
    return np.sort(rng.choice(n_answers, size=k, replace=False))


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}); a number is within its
    limit when it is at most the limit."""
    out = {}
    ok = True
    for name, limit in limits.items():
        value = numbers[name]
        # JSON has no infinity: an unmatched document reads null
        out[name] = {"value": value if np.isfinite(value) else None,
                     "limit": limit}
        ok = ok and bool(value <= limit)
    return ok, out
