"""PQ and OPQ — the quantization math (paper §3.2, Eq. 3–4) and its
codecs (DESIGN.md §7), in one place.

Product Quantization splits an h-dim embedding into ``m`` fragments,
quantizing each fragment to one of ``k`` codewords.  Storage per
document is ``m`` uint8 codes (k ≤ 256) — 32× smaller than fp32 at the
paper's (m=96, k=256, h=768).  Search uses ADC (asymmetric distance
computation): for a query we build a (m, k) inner-product lookup table
once, then score any candidate with an ``m``-gather + sum (Eq. 4).  On
TPU the LUT build is an MXU matmul and the gather-sum is the Pallas
kernel ``repro.kernels.pq_adc``; :func:`adc_score` is the pure-jnp
oracle path.  OPQ (Ge et al. 2014) composes PQ with a learned
orthogonal rotation R so that ``x @ R`` is easier to product-quantize;
scoring reduces to plain PQ once the query is rotated
(``<xR, c> = <x, cRᵀ>``).

``PQCodec`` / ``OPQCodec`` wrap this math behind the codec protocol:
codes are stored uint8 when ``k ≤ 256`` (Faiss's layout: 4× less HBM
and gather traffic than i32 — §Perf, asserted equivalent by
``tests/test_perf_impls.py``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans
from repro.core.codecs import base

Array = jax.Array
#: the precision of every matmul that trains or encodes the codes: float32
#: in full (a TPU's default is one bfloat16 pass), whatever context calls
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# PQ math (folded in from the retired standalone PQ module, PR 4)
# --------------------------------------------------------------------------

class PQCodebook(NamedTuple):
    """codewords: (m, k, dsub) f32 — ``m`` independent sub-codebooks."""
    codewords: Array

    @property
    def m(self) -> int:
        return self.codewords.shape[0]

    @property
    def k(self) -> int:
        return self.codewords.shape[1]

    @property
    def dsub(self) -> int:
        return self.codewords.shape[2]


def split_fragments(x: Array, m: int) -> Array:
    """(n, h) -> (n, m, h/m)."""
    n, h = x.shape
    assert h % m == 0, f"dim {h} not divisible by m={m}"
    return x.reshape(n, m, h // m)


@functools.partial(jax.jit, static_argnames=("m", "k", "n_iters"))
def train_pq(key: Array, x: Array, m: int, k: int = 256,
             n_iters: int = 15) -> PQCodebook:
    """One KMeans per fragment, vmapped over the m independent subspaces."""
    frags = split_fragments(x, m).transpose(1, 0, 2)  # (m, n, dsub)
    keys = jax.random.split(key, m)

    def fit_one(kk, xf):
        c, _ = kmeans.kmeans_fit(kk, xf, n_clusters=k, n_iters=n_iters)
        return c

    codewords = jax.vmap(fit_one)(keys, frags)  # (m, k, dsub)
    return PQCodebook(codewords=codewords)


#: rows per block of the encoders' (n, m, k) distance plane — 2^20 docs
#: at m=96, k=256 would be 103 GB of f32 in one piece
ENCODE_BLOCK = 4096


def _nearest_codes(codebook: PQCodebook, frags: Array) -> Array:
    """Per fragment the codeword of highest <x_j, c> - ||c||²/2 (the
    nearest one): (rows, m, dsub) -> (rows, m) int32.  An argmax over
    8-dim fragments flips on near-ties, so the scores are float32 in
    full (``HIGHEST``) whatever context calls."""
    c = codebook.codewords.astype(jnp.float32)  # (m, k, dsub)
    c_norm = 0.5 * jnp.sum(c * c, axis=-1)  # (m, k)
    scores = jnp.einsum("nmd,mkd->nmk", frags.astype(jnp.float32), c,
                        precision=HIGHEST) - c_norm
    return jnp.argmax(scores, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block",))
def pq_encode(codebook: PQCodebook, x: Array, *,
              block: int = ENCODE_BLOCK) -> Array:
    """Quantize embeddings to codes. (n, h) -> (n, m) int32 (values < k),
    ``block`` rows at a time, at float32 in full (:func:`_nearest_codes`)."""
    return kmeans.map_blocks(
        lambda xi: _nearest_codes(codebook, split_fragments(xi, codebook.m)),
        x, min(block, x.shape[0]))


@functools.partial(jax.jit, static_argnames=("block",))
def pq_decode(codebook: PQCodebook, codes: Array, *,
              block: int = ENCODE_BLOCK) -> Array:
    """Reconstruct embeddings from codes. (n, m) -> (n, h), ``block``
    rows at a time (the (rows, m, dsub) gather pads dsub to a full lane
    tile on TPU, so it must not span the corpus)."""
    def one_block(ci):
        gathered = jnp.take_along_axis(
            codebook.codewords[None],            # (1, m, k, dsub)
            ci[:, :, None, None],                # (block, m, 1, 1)
            axis=2,
        )[:, :, 0]                               # (block, m, dsub)
        return gathered.reshape(ci.shape[0], -1)

    return kmeans.map_blocks(one_block, codes, min(block, codes.shape[0]))


@jax.jit
def adc_lut(codebook: PQCodebook, queries: Array) -> Array:
    """Inner-product lookup tables for a batch of queries.

    (B, h) -> (B, m, k): lut[b, j, i] = <e_Q^j, v_{j,i}>  (Eq. 4 terms).
    """
    qf = split_fragments(queries, codebook.m)  # (B, m, dsub)
    return jnp.einsum("bmd,mkd->bmk", qf.astype(jnp.float32),
                      codebook.codewords.astype(jnp.float32),
                      precision=HIGHEST)


@jax.jit
def adc_score(lut: Array, codes: Array) -> Array:
    """Score candidates against per-query LUTs (pure-jnp oracle path).

    lut: (B, m, k); codes: (B, C, m) int -> scores (B, C) f32.

    Implemented as ONE flat 1-D gather: the take_along_axis formulation
    materializes five (B, C, m, 3) s32 index planes (~18 GB/device at
    the MS MARCO serving point — EXPERIMENTS.md §Perf); flat indexing
    needs a single (B, C, m) i32 plane. (The Pallas kernel sidesteps
    both on TPU; this is the XLA fallback path.)
    """
    b, m, k = lut.shape
    c = codes.shape[1]
    # flatten only (m, k): the batch axis stays leading so its sharding
    # survives (a full flatten forces GSPMD to reshard the LUT)
    lut2 = lut.reshape(b, m * k)
    idx = (jnp.arange(m, dtype=jnp.int32)[None, None, :] * k
           + codes.astype(jnp.int32)).reshape(b, c * m)
    gathered = jnp.take_along_axis(lut2, idx, axis=1)
    return gathered.reshape(b, c, m).sum(axis=-1)


@jax.jit
def pq_full_scores(codebook: PQCodebook, queries: Array, codes: Array) -> Array:
    """Exhaustive PQ scoring of a whole corpus: (B, h) × (n, m) -> (B, n)."""
    lut = adc_lut(codebook, queries)                       # (B, m, k)
    onehot_free = jnp.take_along_axis(
        lut[:, None], codes[None, :, :, None], axis=-1)[..., 0]  # (B, n, m)
    return jnp.sum(onehot_free, axis=-1)


def reconstruction_mse(codebook: PQCodebook, x: Array) -> Array:
    codes = pq_encode(codebook, x)
    return jnp.mean(jnp.sum((pq_decode(codebook, codes) - x) ** 2, axis=-1))


# --------------------------------------------------------------------------
# OPQ math (folded in from the retired standalone OPQ module, PR 4)
# --------------------------------------------------------------------------

class OPQCodebook(NamedTuple):
    rotation: Array        # (h, h) orthogonal
    codebook: PQCodebook

    @property
    def m(self) -> int:
        return self.codebook.m


#: codebook-training points at most — Faiss's cap for OPQ and PQ
#: training (``OPQMatrix::max_train_points`` = 256·256, and 256 points
#: per centroid for k = 256): a larger corpus trains on a random subset
#: of this size, and every document is still encoded
MAX_TRAIN_POINTS = 256 * 256


def train_sample(key: Array, x: Array,
                 max_points: int = MAX_TRAIN_POINTS) -> Array:
    """``x`` itself when it has at most ``max_points`` rows, else a
    random subset of that many rows, in corpus order, seeded from
    ``key`` and drawn on the host."""
    n = x.shape[0]
    if n <= max_points:
        return x
    seed = int(jax.random.randint(jax.random.fold_in(key, n), (), 0,
                                  2 ** 31 - 1))
    pick = np.random.default_rng(seed).choice(n, max_points, replace=False)
    return x[jnp.asarray(np.sort(pick))]


def train_opq(key: Array, x: Array, m: int, k: int = 256,
              n_outer: int = 4, n_kmeans_iters: int = 10) -> OPQCodebook:
    """Standard alternating scheme: PQ-train on rotated data (fix R, fit
    codebooks), then Procrustes-solve for R (fix codebooks: R = U Vᵀ
    from SVD of XᵀX̂, X̂ = decode(encode(XR))).  ``jnp.linalg.svd`` keeps
    everything in JAX; the rotation is h×h (≤ 1024²) so this is cheap
    relative to the KMeans passes.  Trains on :func:`train_sample`."""
    h = x.shape[-1]
    r = jnp.eye(h, dtype=jnp.float32)
    x = train_sample(key, x).astype(jnp.float32)
    cb = None
    for it in range(n_outer):
        key, sub = jax.random.split(key)
        xr = jnp.matmul(x, r, precision=HIGHEST)
        cb = train_pq(sub, xr, m=m, k=k, n_iters=n_kmeans_iters)
        # Procrustes: min_R ||X R - X̂||_F  s.t. RᵀR = I
        xhat = pq_decode(cb, pq_encode(cb, xr))
        u, _, vt = jnp.linalg.svd(jnp.matmul(x.T, xhat, precision=HIGHEST),
                                  full_matrices=False)
        r = jnp.matmul(u, vt, precision=HIGHEST)
    # final codebook on the final rotation
    key, sub = jax.random.split(key)
    cb = train_pq(sub, jnp.matmul(x, r, precision=HIGHEST), m=m, k=k,
                  n_iters=n_kmeans_iters)
    return OPQCodebook(rotation=r, codebook=cb)


@jax.jit
def opq_encode_block(opq: OPQCodebook, xi: Array) -> Array:
    """OPQ codes of one block of rows: (rows, h) -> (rows, m) int32, the
    rotation and the nearest codewords both at float32 in full
    (``HIGHEST``) whatever context calls."""
    xr = jnp.matmul(xi.astype(jnp.float32), opq.rotation, precision=HIGHEST)
    return _nearest_codes(opq.codebook, split_fragments(xr, opq.m))


def opq_encode(opq: OPQCodebook, x: Array) -> Array:
    """OPQ codes of embeddings: (n, h) -> (n, m) int32, one call of
    :func:`opq_encode_block` per ``ENCODE_BLOCK`` rows (the last block
    ends at row n and overlaps the one before).  The stored codes then do
    not depend on the caller's default precision, and no rotated (n, h)
    copy of the corpus exists.  The blocks are calls of their own, not
    the steps of one looped program: on a TPU v5e a program that rotates
    and scores block after block returned codes that were not the
    nearest codeword (PERF.md §7, 1)."""
    n = x.shape[0]
    b = min(ENCODE_BLOCK, n)
    parts = []
    for i in range(0, n, b):
        start = min(i, n - b)
        xi = jax.lax.dynamic_slice_in_dim(x, start, b)
        parts.append(opq_encode_block(opq, xi)[i - start:])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


@jax.jit
def opq_adc_lut(opq: OPQCodebook, queries: Array) -> Array:
    """Rotate the query into codebook space, then the LUT is plain PQ.

    <x R, c> = <x, c Rᵀ> — rotating the query preserves Eq. 4 exactly.
    """
    return adc_lut(opq.codebook,
                   jnp.matmul(queries.astype(jnp.float32), opq.rotation,
                              precision=HIGHEST))


def opq_reconstruction_mse(opq: OPQCodebook, x: Array) -> Array:
    xr = jnp.matmul(x.astype(jnp.float32), opq.rotation, precision=HIGHEST)
    return reconstruction_mse(opq.codebook, xr)


# --------------------------------------------------------------------------
# codecs
# --------------------------------------------------------------------------

def _pack_codes(codes: Array, k: int) -> Array:
    return codes.astype(jnp.uint8) if k <= 256 else codes


def _code_dtype(k: int):
    return jnp.uint8 if k <= 256 else jnp.int32


def _adc_scorer(lut: Array, codes_plane: Array, use_kernel: bool):
    def score(ids: Array, live: Array = None) -> Array:
        if use_kernel:
            # fused path: the (N, m) plane is gathered INSIDE the kernel
            # and the live mask applied in-kernel — no (B, C, m) in HBM
            from repro.kernels.pq_adc import ops as adc_ops
            if live is None:
                live = jnp.ones(ids.shape, jnp.int32)
            return adc_ops.pq_adc_fused(
                lut, codes_plane, jnp.clip(ids, 0, None), live)
        codes = base.gather_rows(codes_plane, ids)       # (B, C, m)
        s = adc_score(lut, codes)
        return s if live is None else jnp.where(live, s, -jnp.inf)

    return score


class PQCodec(base.Codec):
    name = "pq"

    def train(self, key: Array, embeddings: Array, *, pq_m: int = 8,
              pq_k: int = 256) -> PQCodebook:
        return train_pq(key, train_sample(key, embeddings).astype(
            jnp.float32), m=pq_m, k=pq_k)

    def encode(self, params: PQCodebook, embeddings: Array) -> dict:
        return {"codes": _pack_codes(pq_encode(params, embeddings),
                                     params.k)}

    def decode(self, params: PQCodebook, doc_planes: dict) -> Array:
        return pq_decode(params, doc_planes["codes"].astype(jnp.int32))

    def abstract(self, n_docs: int, hidden: int, *, pq_m: int = 8,
                 pq_k: int = 256):
        sds = jax.ShapeDtypeStruct
        params = PQCodebook(
            codewords=sds((pq_m, pq_k, hidden // pq_m), jnp.float32))
        return params, {"codes": sds((n_docs, pq_m), _code_dtype(pq_k))}

    def make_scorer(self, params: PQCodebook, doc_planes: dict,
                    queries: Array, use_kernel: bool = False):
        lut = adc_lut(params, queries)                   # (B, m, k)
        return _adc_scorer(lut, doc_planes["codes"], use_kernel)


class OPQCodec(PQCodec):
    """OPQ codes (paper §3.2): ``m`` uint8 codes a document over the
    rotated corpus.  Training's and encoding's matmuls state float32 in
    full (``HIGHEST``), so the code plane is the nearest codeword at
    every position however the caller sets the default precision: a
    single bfloat16 pass flips near-ties of the argmax.  Encoding runs
    one call per block of rows (:func:`opq_encode`)."""

    name = "opq"

    def train(self, key: Array, embeddings: Array, *, pq_m: int = 8,
              pq_k: int = 256) -> OPQCodebook:
        return train_opq(key, embeddings, m=pq_m, k=pq_k)

    def encode(self, params: OPQCodebook, embeddings: Array) -> dict:
        return {"codes": _pack_codes(opq_encode(params, embeddings),
                                     params.codebook.k)}

    def decode(self, params: OPQCodebook, doc_planes: dict) -> Array:
        # decode in rotated space, rotate back (R orthogonal: R⁻¹ = Rᵀ)
        xr = pq_decode(params.codebook,
                       doc_planes["codes"].astype(jnp.int32))
        return jnp.matmul(xr, params.rotation.T, precision=HIGHEST)

    def abstract(self, n_docs: int, hidden: int, *, pq_m: int = 8,
                 pq_k: int = 256):
        sds = jax.ShapeDtypeStruct
        cb, planes = PQCodec.abstract(self, n_docs, hidden,
                                      pq_m=pq_m, pq_k=pq_k)
        params = OPQCodebook(
            rotation=sds((hidden, hidden), jnp.float32), codebook=cb)
        return params, planes

    def make_scorer(self, params: OPQCodebook, doc_planes: dict,
                    queries: Array, use_kernel: bool = False):
        # <xR, c> = <x, cRᵀ>: rotating the query reduces OPQ to PQ (Eq. 4)
        lut = opq_adc_lut(params, queries)
        return _adc_scorer(lut, doc_planes["codes"], use_kernel)
