"""The benchmark's corpus and query pool, made on the device from a seed.

A copy of the generative model of the program's synthetic corpus
(topic centres, Zipf background terms, topical and rare salient terms;
easy queries near their positive document, hard ones pulled towards
another topic), kept here so that a change to the program cannot change
the benchmark's data.  Everything is one jitted call from the seed, in
float32 and int32, on the default device.

The configuration's ``corpus`` group gives the model's parameters.  The
noise scales are per dimension (``scale / sqrt(hidden)``), so the
norm of the noise stays fixed as the width grows: at the published
768 dimensions the unscaled defaults would swamp the topics, and the
exact top-100 of a query would be noise.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np


class Corpus(NamedTuple):
    doc_emb: object        # (n_docs, h) f32, unit rows
    doc_tokens: object     # (n_docs, doc_len) i32, no padding
    query_emb: object      # (pool, h) f32, unit rows
    query_tokens: object   # (pool, query_len) i32


def seed32(seed: int) -> int:
    """Any whole number (seeds may pass 2**31) to 32 bits."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _zipf_cdf(v: int, s: float = 1.07) -> np.ndarray:
    p = 1.0 / np.arange(1, v + 1) ** s
    return np.cumsum(p / p.sum())


def generate(seed: int, cfg: dict, pool: int) -> Corpus:
    """The corpus of configuration ``cfg`` and a pool of ``pool``
    queries, from ``seed``."""
    import jax

    c = cfg["corpus"]
    fn = _generator(cfg["n_docs"], pool, cfg["hidden"], cfg["vocab"],
                    cfg["n_clusters"], c["doc_len"], cfg["query_len"],
                    tuple(sorted(c.items())))
    out = fn(jax.random.key(seed32(seed)))
    jax.block_until_ready(out)
    return Corpus(*out)


@functools.lru_cache(maxsize=4)
def _generator(n_docs, n_queries, hidden, vocab_size, n_topics, doc_len,
               query_len, params):
    import jax
    import jax.numpy as jnp

    p = dict(params)
    per_dim = hidden ** -0.5
    sigma_doc = p["doc_noise"] * per_dim
    sigma_idio = p["idio_noise"] * per_dim
    sigma_easy = p["easy_noise"] * per_dim
    sigma_hard = p["hard_noise"] * per_dim
    p_hard, mix = p["p_hard"], p["hard_topic_mix"]
    p_lexical, topical_terms = p["p_lexical"], p["topical_terms"]
    salient_per_doc = p["salient_per_doc"]
    cdf = _zipf_cdf(vocab_size)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 20))
        zcdf = jnp.asarray(cdf, jnp.float32)

        def normal(shape):
            return jax.random.normal(next(keys), shape, jnp.float32)

        def uniform(shape):
            return jax.random.uniform(next(keys), shape)

        def randint(shape, lo, hi):
            return jax.random.randint(next(keys), shape, lo, hi, jnp.int32)

        def zipf(shape):
            return jnp.minimum(jnp.searchsorted(zcdf, uniform(shape)),
                               vocab_size - 1).astype(jnp.int32)

        def normalize(x):
            return x / jnp.maximum(
                jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)

        def pick(table, rows, n_cols, width):
            cols = randint((rows.shape[0], n_cols), 0, width)
            return jnp.take_along_axis(table[rows], cols, axis=1)

        centers = normalize(normal((n_topics, hidden)))
        topic_terms = randint((n_topics, topical_terms), vocab_size // 16,
                              vocab_size // 2)

        doc_topic = randint((n_docs,), 0, n_topics)
        doc_emb = normalize(centers[doc_topic]
                            + normal((n_docs, hidden)) * sigma_doc
                            + normal((n_docs, hidden)) * sigma_idio)
        n_top = doc_len // 3
        salient = randint((n_docs, salient_per_doc), vocab_size // 2,
                          vocab_size)
        doc_tokens = jnp.concatenate(
            [zipf((n_docs, doc_len - n_top - salient_per_doc)),
             pick(topic_terms, doc_topic, n_top, topical_terms), salient], 1)
        doc_tokens = jnp.take_along_axis(
            doc_tokens, jnp.argsort(uniform(doc_tokens.shape), axis=1),
            axis=1)

        qrels = randint((n_queries,), 0, n_docs)
        is_hard = uniform((n_queries,)) < p_hard
        pos_emb = doc_emb[qrels]
        hard_emb = normalize(
            (1 - mix) * pos_emb
            + mix * centers[randint((n_queries,), 0, n_topics)]
            + normal((n_queries, hidden)) * sigma_hard)
        easy_emb = normalize(pos_emb + normal((n_queries, hidden))
                             * sigma_easy)
        query_emb = jnp.where(is_hard[:, None], hard_emb, easy_emb)

        n_sal_q = min(2, salient_per_doc)
        has_lex = uniform((n_queries, 1)) < p_lexical
        q_sal = jnp.where(has_lex, salient[qrels][:, :n_sal_q],
                          zipf((n_queries, n_sal_q)))
        n_top_q = (query_len - n_sal_q) // 2
        query_tokens = jnp.concatenate(
            [q_sal, pick(topic_terms, doc_topic[qrels], n_top_q,
                         topical_terms),
             zipf((n_queries, query_len - n_sal_q - n_top_q))], 1)
        return (doc_emb, doc_tokens.astype(jnp.int32), query_emb,
                query_tokens.astype(jnp.int32))

    return make
