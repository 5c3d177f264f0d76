"""Flat codec — full-precision embeddings, exact inner product
(DESIGN.md §7).  The quality upper bound every other codec is measured
against (paper Table 3); doc-plane cost is 4·h bytes/doc.

Also home of :func:`search`, the brute-force top-k over a whole corpus
(folded in from the retired standalone flat-search module in PR 4):
the exact-retrieval oracle benchmarks and
tests measure every index against, blocked so the (B, n_docs) score
plane never materializes for large corpora.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.codecs import base

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("k", "block"))
def search(query_embeddings: Array, doc_embeddings: Array, k: int,
           block: int = 65536) -> tuple[Array, Array]:
    """Exact top-k by inner product. Returns (scores (B,k), ids (B,k))."""
    b = query_embeddings.shape[0]
    n, h = doc_embeddings.shape
    q = query_embeddings.astype(jnp.float32)

    n_blocks = -(-n // block)
    pad = n_blocks * block - n
    docs = jnp.pad(doc_embeddings.astype(jnp.float32), ((0, pad), (0, 0)))
    docs = docs.reshape(n_blocks, block, h)

    def body(carry, xs):
        best_s, best_i = carry
        blk, blk_idx = xs
        s = jnp.matmul(q, blk.T, precision=jax.lax.Precision.HIGHEST)
        ids = blk_idx * block + jnp.arange(block)
        valid = ids < n
        s = jnp.where(valid[None], s, -jnp.inf)
        cat_s = jnp.concatenate([best_s, s], axis=-1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (b, block))], axis=-1)
        top_s, top_pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, top_pos, axis=-1)
        return (top_s, top_i), None

    init = (jnp.full((b, k), -jnp.inf), jnp.full((b, k), -1, jnp.int32))
    (scores, ids), _ = jax.lax.scan(
        body, init, (docs, jnp.arange(n_blocks)))
    return scores, ids.astype(jnp.int32)


class FlatCodec(base.Codec):
    name = "flat"

    def encode(self, params, embeddings: Array) -> dict:
        return {"emb": jnp.asarray(embeddings, jnp.float32)}

    def decode(self, params, doc_planes: dict) -> Array:
        return doc_planes["emb"]

    def abstract(self, n_docs: int, hidden: int, *, pq_m: int = 8,
                 pq_k: int = 256):
        return None, {"emb": jax.ShapeDtypeStruct((n_docs, hidden),
                                                  jnp.float32)}

    def make_scorer(self, params, doc_planes: dict, queries: Array,
                    use_kernel: bool = False):
        # no fused kernel for flat: the fp32 plane's gather IS the score
        # input (h floats/doc, no decode step), so a fused op would save
        # nothing — ``use_kernel`` is accepted and ignored (the
        # documented fallback, DESIGN.md §11)
        q = queries.astype(jnp.float32)
        emb = doc_planes["emb"]

        def score(ids: Array, live: Array = None) -> Array:
            rows = base.gather_rows(emb, ids)            # (B, C, h)
            s = jnp.einsum("bh,bch->bc", q, rows,
                           precision=jax.lax.Precision.HIGHEST)
            return s if live is None else jnp.where(live, s, -jnp.inf)

        return score
