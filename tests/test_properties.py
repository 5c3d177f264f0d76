"""Property-based tests (hypothesis) on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hybrid_index as hi, inverted_lists as il
from repro.data import synthetic

settings.register_profile("props", max_examples=8, deadline=None)
settings.load_profile("props")


@pytest.fixture(scope="module")
def small_index():
    corpus = synthetic.generate(seed=7, n_docs=3000, n_queries=64,
                                hidden=32, vocab_size=1024, n_topics=32)
    idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
                   jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                   n_clusters=48, k1_terms=6, codec="opq", pq_m=4, pq_k=64,
                   cluster_capacity=128, term_capacity=64, kmeans_iters=5)
    return corpus, idx


@given(kc=st.integers(1, 8), k2=st.integers(1, 8), top_r=st.integers(1, 64))
def test_search_invariants(small_index, kc, k2, top_r):
    corpus, idx = small_index
    qe = jnp.asarray(corpus.query_emb[:16])
    qt = jnp.asarray(corpus.query_tokens[:16])
    res = hi.search(idx, qe, qt, kc=kc, k2=k2, top_r=top_r)
    ids = np.asarray(res.doc_ids)
    scores = np.asarray(res.scores)
    n_docs = corpus.doc_emb.shape[0]
    for q in range(ids.shape[0]):
        valid = ids[q][ids[q] != il.PAD_DOC]
        # unique results, in-range ids
        assert len(set(valid.tolist())) == len(valid)
        assert ((valid >= 0) & (valid < n_docs)).all()
        # scores sorted descending over valid prefix
        vs = scores[q][:len(valid)]
        assert np.all(np.diff(vs) <= 1e-5)
    # candidate count bounded by the static budget
    assert int(np.asarray(res.n_candidates).max()) <= \
        hi.candidate_budget(idx, kc, k2)


@pytest.fixture(scope="module")
def flat_index(small_index):
    corpus, _ = small_index
    return hi.build(jax.random.key(1), jnp.asarray(corpus.doc_emb),
                    jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                    n_clusters=48, k1_terms=6, codec="flat",
                    cluster_capacity=128, term_capacity=64, kmeans_iters=5)


@given(kc=st.integers(1, 6), k2=st.integers(1, 6))
def test_widening_dispatch_never_hurts_recall(small_index, flat_index,
                                              kc, k2):
    """Monotonicity under EXACT scoring: a superset of dispatched lists ⇒
    recall cannot drop. (hypothesis originally REFUTED this for the PQ
    codec — approximate scores can rank new candidates above the true
    positive — so the theorem is asserted where it holds: Flat codec.)"""
    from repro.core import metrics
    corpus, _ = small_index
    idx = flat_index
    qe = jnp.asarray(corpus.query_emb)
    qt = jnp.asarray(corpus.query_tokens)
    narrow = hi.search(idx, qe, qt, kc=kc, k2=k2, top_r=200)
    wide = hi.search(idx, qe, qt, kc=kc + 4, k2=k2 + 4, top_r=200)
    r_n = metrics.recall_at_k(narrow.doc_ids, corpus.qrels, 200)
    r_w = metrics.recall_at_k(wide.doc_ids, corpus.qrels, 200)
    assert r_w >= r_n - 1e-9


@given(n=st.integers(10, 200), n_lists=st.integers(2, 12))
def test_dedup_mask_is_exact_set_semantics(n, n_lists):
    rng = np.random.default_rng(n * n_lists)
    cands = rng.integers(-1, 50, size=(3, n)).astype(np.int32)
    keep = np.asarray(il.dedup_mask(jnp.asarray(cands)))
    for row in range(3):
        kept = cands[row][keep[row]]
        expected = set(cands[row][cands[row] != il.PAD_DOC].tolist())
        assert set(kept.tolist()) == expected
        assert len(kept) == len(expected)


@given(seed=st.integers(0, 5))
def test_flat_codec_search_contains_embedding_topk_of_candidates(
        small_index, seed):
    """With the Flat codec, the returned order equals exact inner-product
    order restricted to the candidate set."""
    corpus, _ = small_index
    idx = hi.build(jax.random.key(seed), jnp.asarray(corpus.doc_emb),
                   jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                   n_clusters=48, k1_terms=6, codec="flat",
                   cluster_capacity=128, term_capacity=64, kmeans_iters=3)
    qe = jnp.asarray(corpus.query_emb[:4])
    qt = jnp.asarray(corpus.query_tokens[:4])
    res = hi.search(idx, qe, qt, kc=4, k2=4, top_r=10)
    ids = np.asarray(res.doc_ids)
    scores = np.asarray(res.scores)
    emb = np.asarray(corpus.doc_emb)
    q = np.asarray(corpus.query_emb[:4])
    for i in range(4):
        valid = ids[i][ids[i] != il.PAD_DOC]
        expect = q[i] @ emb[valid].T
        np.testing.assert_allclose(scores[i][:len(valid)], expect,
                                   rtol=1e-4, atol=1e-4)
