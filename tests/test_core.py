"""Unit + property tests for the HI² core numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (bm25, cluster_selector as cs, inverted_lists as il,
                        kmeans, pruning, term_selector as ts)
from repro.core.codecs import pq

settings.register_profile("core", max_examples=10, deadline=None)
settings.load_profile("core")


# --------------------------------------------------------------------------
# kmeans
# --------------------------------------------------------------------------

def test_kmeans_reduces_cost_and_assigns_all():
    key = jax.random.key(0)
    x = jax.random.normal(key, (2000, 16))
    c0 = x[jax.random.choice(jax.random.key(1), 2000, (32,), replace=False)]
    a0 = kmeans.assign_blocked(x, c0)
    cost0 = kmeans.kmeans_cost(x, c0, a0)
    c, a = kmeans.kmeans_fit(jax.random.key(1), x, n_clusters=32, n_iters=10)
    assert float(kmeans.kmeans_cost(x, c, a)) < float(cost0)
    assert int(a.min()) >= 0 and int(a.max()) < 32


def test_kmeans_assignment_is_nearest():
    key = jax.random.key(2)
    x = jax.random.normal(key, (500, 8))
    c, a = kmeans.kmeans_fit(jax.random.key(3), x, n_clusters=16, n_iters=5)
    d = np.linalg.norm(np.asarray(x)[:, None] - np.asarray(c)[None], axis=-1)
    np.testing.assert_array_equal(np.asarray(a), d.argmin(axis=1))


# --------------------------------------------------------------------------
# pq / opq
# --------------------------------------------------------------------------

@given(m=st.sampled_from([2, 4, 8]), n=st.integers(300, 800))
def test_pq_reconstruction_better_than_random(m, n):
    key = jax.random.key(m * n)
    x = jax.random.normal(key, (n, 32))
    cb = pq.train_pq(jax.random.fold_in(key, 1), x, m=m, k=16, n_iters=6)
    mse = float(pq.reconstruction_mse(cb, x))
    assert mse < float(jnp.mean(jnp.sum(x * x, axis=-1)))  # beats zero codes


def test_pq_adc_equals_decoded_inner_product():
    """Eq. 4: ADC score == ⟨q, decode(code)⟩ exactly."""
    key = jax.random.key(5)
    x = jax.random.normal(key, (400, 32))
    q = jax.random.normal(jax.random.fold_in(key, 1), (8, 32))
    cb = pq.train_pq(jax.random.fold_in(key, 2), x, m=4, k=16, n_iters=5)
    codes = pq.pq_encode(cb, x)
    lut = pq.adc_lut(cb, q)
    cand = jnp.broadcast_to(jnp.arange(50)[None], (8, 50))
    scores = pq.adc_score(lut, codes[cand])
    expect = q @ pq.pq_decode(cb, codes[:50]).T
    np.testing.assert_allclose(np.asarray(scores), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_opq_rotation_is_orthogonal_and_helps():
    key = jax.random.key(6)
    # anisotropic data — the regime OPQ exists for
    scales = jnp.concatenate([jnp.ones(4) * 4.0, jnp.ones(28) * 0.3])
    x = jax.random.normal(key, (1500, 32)) * scales
    o = pq.train_opq(jax.random.fold_in(key, 1), x, m=4, k=16,
                      n_outer=3, n_kmeans_iters=5)
    r = np.asarray(o.rotation)
    np.testing.assert_allclose(r @ r.T, np.eye(32), atol=1e-4)
    cb = pq.train_pq(jax.random.fold_in(key, 2), x, m=4, k=16, n_iters=5)
    assert float(pq.opq_reconstruction_mse(o, x)) <= \
        float(pq.reconstruction_mse(cb, x)) * 1.05


# --------------------------------------------------------------------------
# bm25 / term selection
# --------------------------------------------------------------------------

def _toy_corpus():
    # doc0 repeats term 7; term 9 appears only in doc1 (high IDF)
    return jnp.array([[7, 7, 7, 1, 2, -1],
                      [9, 1, 2, 3, -1, -1],
                      [1, 2, 3, 4, 5, 6]], jnp.int32)


def test_bm25_idf_favors_rare_terms():
    toks = _toy_corpus()
    stats = bm25.fit(toks, vocab_size=16)
    idf = np.asarray(stats.idf)
    assert idf[9] > idf[1]          # term 9 in 1 doc, term 1 in 3 docs
    assert idf[9] > idf[2]


def test_bm25_tf_saturation():
    """Repeats help sub-linearly (the BM25 point)."""
    toks = _toy_corpus()
    stats = bm25.fit(toks, vocab_size=16)
    s = np.asarray(bm25.score_positions(toks, stats))
    one_seven = s[0][np.asarray(toks[0]) == 7][0]
    # score of tf=3 occurrence < 3× a hypothetical tf=1 score
    toks1 = toks.at[0, 1].set(10).at[0, 2].set(11)
    s1 = np.asarray(bm25.score_positions(toks1, bm25.fit(toks1, 16)))
    one_seven_tf1 = s1[0][np.asarray(toks1[0]) == 7][0]
    assert one_seven < 3 * one_seven_tf1


def test_first_occurrence_and_top_terms():
    toks = _toy_corpus()
    first = np.asarray(bm25.first_occurrence_mask(toks))
    assert first[0].tolist() == [True, False, False, True, True, False]
    stats = bm25.fit(toks, vocab_size=16)
    scores = bm25.score_positions(toks, stats)
    ids, sc = bm25.top_terms(toks, scores, k=2)
    assert ids.shape == (3, 2)
    # every selected term actually occurs in its doc
    for i in range(3):
        for t in np.asarray(ids[i]):
            if t != bm25.PAD_ID:
                assert t in np.asarray(toks[i])


def test_score_vector_max_pools_repeats():
    toks = jnp.array([[5, 5, -1]], jnp.int32)
    pos = jnp.array([[2.0, 3.0, 0.0]])
    v = bm25.score_vector(toks, pos, vocab_size=8)
    assert float(v[0, 5]) == 3.0
    assert float(v[0].sum()) == 3.0


def test_query_terms_short_query_selects_all():
    """Eq. 8: |Q| ≤ K₂ᵀ → all unique terms dispatched."""
    sel = ts.TermSelector(avg_scores=jnp.arange(16, dtype=jnp.float32))
    q = jnp.array([[3, 5, -1, -1]], jnp.int32)
    out = np.asarray(ts.query_terms(sel, q, k2=8))
    assert set(out[0]) - {-1} == {3, 5}


def test_query_terms_long_query_selects_top_sbar():
    sel = ts.TermSelector(avg_scores=jnp.arange(16, dtype=jnp.float32))
    q = jnp.array([[1, 9, 3, 14, 2, 7]], jnp.int32)
    out = np.asarray(ts.query_terms(sel, q, k2=3))
    assert set(out[0]) == {14, 9, 7}     # top-3 by s̄


# --------------------------------------------------------------------------
# inverted lists / pruning
# --------------------------------------------------------------------------

@given(n=st.integers(20, 300), n_lists=st.integers(2, 20),
       cap=st.integers(1, 16))
def test_build_respects_capacity_and_membership(n, n_lists, cap):
    rng = np.random.default_rng(n)
    docs = rng.integers(0, 10_000, n)
    lists = rng.integers(0, n_lists, n)
    scores = rng.normal(size=n)
    pl = il.build(docs, lists, scores, n_lists=n_lists, capacity=cap)
    assert pl.entries.shape == (n_lists, cap)
    e = np.asarray(pl.entries)
    lengths = np.asarray(pl.lengths)
    for li in range(n_lists):
        members = set(docs[lists == li].tolist())
        stored = [d for d in e[li] if d != il.PAD_DOC]
        assert len(stored) == min(len(docs[lists == li]), cap) == lengths[li]
        assert set(stored) <= members
        # kept entries are the top-scored ones
        if len(docs[lists == li]) > cap:
            kept_scores = sorted(scores[lists == li])[-cap:]
            got = sorted(scores[(lists == li) & np.isin(docs, stored)])[-cap:]
            np.testing.assert_allclose(got, kept_scores)


def test_dedup_mask_keeps_exactly_first_occurrences():
    cands = jnp.array([[3, 5, 3, -1, 5, 7]], jnp.int32)
    keep = np.asarray(il.dedup_mask(cands))[0]
    kept = np.asarray(cands)[0][keep]
    assert sorted(kept.tolist()) == [3, 5, 7]


def _first_occurrence_mask(cands: np.ndarray) -> np.ndarray:
    """numpy reference: True at the first slot of each non-PAD id."""
    mask = np.zeros(cands.shape, bool)
    for r, row in enumerate(cands):
        _, first = np.unique(row, return_index=True)
        mask[r, first] = True
    return mask & (cands != il.PAD_DOC)


def _served_plane(b: int = 4, c: int = 63_488) -> np.ndarray:
    """A candidate plane at the served width: 62 lists of 1,024 slots,
    each list a run of ids from a shared pool of 20,000 docs (heavy
    cluster ∩ term duplication) followed by PAD to its capacity."""
    rng = np.random.default_rng(16)
    lists = rng.integers(0, 20_000, size=(b, c // 1024, 1024), dtype=np.int32)
    lengths = rng.integers(0, 1025, size=(b, c // 1024, 1))
    lists[np.arange(1024) >= lengths] = il.PAD_DOC
    return lists.reshape(b, c)


_DEDUP_CASES = {
    "all_pad": np.full((2, 16), il.PAD_DOC, np.int32),
    "all_duplicate": np.full((2, 16), 7, np.int32),
    "c_is_1": np.array([[5], [il.PAD_DOC], [0]], np.int32),
    "no_duplicates": np.stack([np.random.default_rng(r).permutation(64)
                               for r in range(3)]).astype(np.int32),
    "extreme_ids": np.array([[2**31 - 1, 0, il.PAD_DOC, 0, 2**31 - 1,
                              il.PAD_DOC]], np.int32),
    "mixed_small": np.random.default_rng(3).integers(
        -1, 6, size=(5, 40)).astype(np.int32),
    "served_width": _served_plane(),
}


@pytest.mark.parametrize("case", list(_DEDUP_CASES))
def test_dedup_mask_matches_first_occurrence_slot_by_slot(case):
    """Not just the set of kept ids: the kept slot of each id is its
    first in candidate order (DESIGN.md §6/§9 bit-identity rests on it)."""
    cands = _DEDUP_CASES[case]
    keep = np.asarray(il.dedup_mask(jnp.asarray(cands)))
    assert keep.dtype == bool and keep.shape == cands.shape
    np.testing.assert_array_equal(keep, _first_occurrence_mask(cands))


def _primitives(jaxpr) -> list[str]:
    """Names of every primitive of a jaxpr, nested jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _primitives(sub)
    return names


@pytest.mark.parametrize("shape", [(1, 1), (3, 40), (64, 63_488)])
def test_dedup_mask_sorts_twice_and_never_gathers(shape):
    """Both permuted values ride sort payloads: a gather over the C
    slots costs ~10x the sorts on a TPU."""
    prims = _primitives(jax.make_jaxpr(il.dedup_mask)(
        jax.ShapeDtypeStruct(shape, jnp.int32)).jaxpr)
    assert prims.count("sort") == 2, prims
    assert not [p for p in prims if p.startswith(("gather", "scatter"))]


def test_pruning_truncates_to_percentile():
    rng = np.random.default_rng(0)
    docs = np.arange(1000)
    lists = np.concatenate([np.zeros(500, int), rng.integers(1, 50, 500)])
    pl = il.build(docs, lists, rng.normal(size=1000), n_lists=50)
    pruned = pruning.prune_percentile(pl, gamma=0.9)
    assert pruned.capacity < pl.capacity
    assert int(np.asarray(pruned.lengths).max()) <= pruned.capacity


# --------------------------------------------------------------------------
# cluster selector
# --------------------------------------------------------------------------

def test_cluster_selector_doc_goes_to_argmax():
    key = jax.random.key(8)
    docs = jax.random.normal(key, (200, 16))
    sel, assign = cs.init_kmeans(jax.random.key(9), docs, n_clusters=8,
                                 n_iters=5)
    s = np.asarray(cs.scores(sel, docs))
    np.testing.assert_array_equal(np.asarray(assign), s.argmax(axis=1))
    top_i, top_s = cs.select_for_query(sel, docs[:10], k=3)
    assert top_i.shape == (10, 3)
    np.testing.assert_array_equal(np.asarray(top_i[:, 0]),
                                  s[:10].argmax(axis=1))
