"""Codec subsystem numerics and registry contracts (DESIGN.md §7).

Every registered codec goes through the same build → search path, so
these tests pin the seam itself: registry resolution errors, per-codec
encode/score round trips against the decode oracle, the sq8
quantization-error bound, uint8/i32 code equivalence, the PQ/OPQ
encoders' float32-in-full code plane whatever the caller's default
precision, the refine codec's "lossless when R′ covers the budget"
guarantee, and codec-validated checkpointing.
"""
import dataclasses
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codecs, hybrid_index as hi
from repro.core import segments as seg
from repro.core.codecs import base as codecs_base, pq
from repro.data import synthetic

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

KEY = jax.random.key(0)


def _corpus(n_docs=2000, hidden=32, f16_exact=False):
    c = synthetic.generate(seed=0, n_docs=n_docs, n_queries=32,
                           hidden=hidden, vocab_size=1024, n_topics=16)
    doc_emb = np.asarray(c.doc_emb)
    if f16_exact:
        # embeddings exactly representable in fp16, so the refine
        # plane's cast is lossless and scores can be compared bitwise
        doc_emb = doc_emb.astype(np.float16).astype(np.float32)
    return dataclasses.replace(c, doc_emb=doc_emb)


def _build(corpus, codec, **overrides):
    kwargs = dict(n_clusters=32, k1_terms=6, codec=codec, pq_m=4, pq_k=64,
                  cluster_capacity=96, term_capacity=48, kmeans_iters=5)
    kwargs.update(overrides)
    return hi.build(KEY, jnp.asarray(corpus.doc_emb),
                    jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                    **kwargs)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

def test_registry_unknown_codec_lists_known_names():
    with pytest.raises(ValueError) as exc:
        codecs.get("no_such_codec")
    msg = str(exc.value)
    assert "no_such_codec" in msg
    for name in codecs.registered():
        assert name in msg


def test_registry_covers_expected_codecs_and_caches():
    names = codecs.registered()
    for expected in ("flat", "pq", "opq", "sq8", "refine"):
        assert expected in names
    assert codecs.get("sq8") is codecs.get("sq8")          # cached per spec
    assert codecs.get("refine").name == "refine:pq:4"      # defaults
    assert codecs.get("refine:sq8:2").mult == 2


def test_build_rejects_unknown_codec():
    c = _corpus(n_docs=200)
    with pytest.raises(ValueError, match="registered codecs"):
        _build(c, "not_a_codec")


# --------------------------------------------------------------------------
# per-codec numerics: scorer == <q, decode(encode(x))>
# --------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["flat", "pq", "opq", "sq8", "refine"])
def test_scorer_matches_decode_oracle(spec):
    """Stage-1 scoring must equal the inner product against the codec's
    reconstruction — the property that makes ``decode`` an oracle."""
    impl = codecs.get(spec)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (500, 32))
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (8, 32))
    params = impl.train(jax.random.fold_in(KEY, 3), x, pq_m=4, pq_k=16)
    planes = impl.encode(params, x)
    ids = jnp.tile(jnp.arange(64, dtype=jnp.int32)[None], (8, 1))
    got = impl.make_scorer(params, planes, q)(ids)
    # a refining codec's stage-1 scorer is its base codec's scorer
    oracle = impl.base if isinstance(impl, codecs.refine.RefineCodec) else impl
    want = np.asarray(q) @ np.asarray(oracle.decode(params, planes)).T[:, :64]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3, atol=1e-3)


def test_sq8_reconstruction_error_bound():
    """Affine min/max quantization: per-dim error ≤ scale/2, and codes
    span the full byte range at the extremes."""
    impl = codecs.get("sq8")
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (1000, 16)) * 3.0
    params = impl.train(KEY, x)
    planes = impl.encode(params, x)
    err = np.abs(np.asarray(impl.decode(params, planes)) - np.asarray(x))
    bound = np.asarray(params["scale"]) / 2 + 1e-6
    assert (err <= bound[None, :]).all()
    codes = np.asarray(planes["codes"])
    assert codes.min() == 0 and codes.max() == 255


def test_sq8_constant_dimension_is_exact():
    impl = codecs.get("sq8")
    x = jnp.concatenate([jnp.full((100, 1), 2.5),
                         jax.random.normal(KEY, (100, 3))], axis=-1)
    params = impl.train(KEY, x)
    rec = np.asarray(impl.decode(params, impl.encode(params, x)))
    np.testing.assert_allclose(rec[:, 0], 2.5, rtol=0, atol=0)


@pytest.mark.parametrize("spec", ["pq", "opq"])
def test_pq_codes_pack_to_uint8_iff_small_k(spec):
    impl = codecs.get(spec)
    x = jax.random.normal(KEY, (300, 16))
    for pq_k, dtype in ((16, jnp.uint8), (300, jnp.int32)):
        params = impl.train(jax.random.fold_in(KEY, pq_k), x,
                            pq_m=4, pq_k=pq_k)
        codes = impl.encode(params, x)["codes"]
        assert codes.dtype == dtype, (spec, pq_k)


# (uint8 vs i32 code *search* equivalence lives with the other §Perf
# claims in tests/test_perf_impls.py)


# --------------------------------------------------------------------------
# PQ/OPQ encoding: float32 in full whatever the caller's default precision
# --------------------------------------------------------------------------

#: the paper's OPQ widths (§5.1): h, m, k
H, M, K = 768, 96, 256
#: three whole encode blocks and a ragged one
N_RAGGED = 3 * pq.ENCODE_BLOCK + 5


def _published_opq(rotate: bool, seed: int = 0) -> pq.OPQCodebook:
    """A random orthogonal rotation (the identity for plain PQ) and
    random codewords at the published widths."""
    rng = np.random.default_rng(seed)
    rot = (np.linalg.qr(rng.standard_normal((H, H)))[0] if rotate
           else np.eye(H))
    cw = rng.standard_normal((M, K, H // M)) / np.sqrt(H)
    return pq.OPQCodebook(
        rotation=jnp.asarray(rot, jnp.float32),
        codebook=pq.PQCodebook(codewords=jnp.asarray(cw, jnp.float32)))


_DOT = re.compile(r"stablehlo\.dot_general .*")
_DIMS = re.compile(r"tensor<((?:\d+x)+)[a-z]")


@pytest.mark.parametrize("context", ["bfloat16", "highest"])
@pytest.mark.parametrize("encoder", ["opq", "pq"])
def test_encoders_state_full_precision_per_block(encoder, context):
    """Every product of the encoder is lowered at HIGHEST whatever the
    context says, and none spans more than one block of rows."""
    opq = _published_opq(rotate=True)
    x = jax.ShapeDtypeStruct((N_RAGGED, H), jnp.float32)
    fn = pq.opq_encode if encoder == "opq" else pq.pq_encode
    arg = opq if encoder == "opq" else opq.codebook
    with jax.default_matmul_precision(context):
        text = jax.jit(fn).lower(arg, x).as_text()
    dots = _DOT.findall(text)
    assert dots
    for line in dots:
        assert "precision = [HIGHEST, HIGHEST]" in line, line
        dims = [int(d) for t in _DIMS.findall(line)
                for d in t.rstrip("x").split("x")]
        assert max(dims) <= pq.ENCODE_BLOCK, line


@pytest.fixture(scope="module")
def ragged_opq():
    """Seeded rows over a ragged last block, an OPQ codebook at the
    published widths, the float64 nearest codeword at each position,
    and the positions the benchmark's reference leaves open as
    near-ties (``derive.opq_codes``; slow on the CPU, so made once)."""
    from bench import derive

    opq = _published_opq(rotate=True, seed=1)
    x = np.random.default_rng(2).standard_normal((N_RAGGED, H)).astype(
        np.float32) / np.float32(np.sqrt(H))
    rot = np.asarray(opq.rotation)
    cw = np.asarray(opq.codebook.codewords)
    ref = derive.opq_codes(jnp.asarray(x), rot, cw, lambda ids: x[ids])
    xr = (x.astype(np.float64) @ rot.astype(np.float64)).reshape(
        N_RAGGED, M, H // M).transpose(1, 0, 2)
    cw64 = cw.astype(np.float64)
    s = xr @ cw64.transpose(0, 2, 1) - 0.5 * (cw64 ** 2).sum(-1)[:, None]
    sure = np.ones((N_RAGGED, M), bool)
    sure[ref.alt_doc, ref.alt_pos] = False
    return opq, x, np.argmax(s, axis=-1).T, sure


@pytest.mark.parametrize("encoder", ["opq", "pq"])
def test_encoders_give_the_float64_nearest_codeword(ragged_opq, encoder):
    """At the published widths, over a ragged last block, the codes are
    the float64 nearest codeword at every position the reference does
    not leave open; plain PQ encodes the rows rotated beforehand."""
    opq, x, nearest, sure = ragged_opq
    assert sure.mean() > 0.99
    with jax.default_matmul_precision("bfloat16"):
        if encoder == "opq":
            codes = pq.opq_encode(opq, jnp.asarray(x))
        else:
            xr = jnp.matmul(jnp.asarray(x), opq.rotation,
                            precision=pq.HIGHEST)
            codes = pq.pq_encode(opq.codebook, xr)
    np.testing.assert_array_equal(np.asarray(codes)[sure], nearest[sure])


def test_delta_inserts_store_the_base_encode():
    """Docs streamed into the mutable index get the codes the base build
    gave the same rows, whatever the precision each was called under."""
    c = _corpus(n_docs=600)
    with jax.default_matmul_precision("highest"):
        mut = seg.MutableHybridIndex.create(
            KEY, c.doc_emb, c.doc_tokens, c.vocab_size, delta_capacity=64,
            n_clusters=16, k1_terms=4, codec="opq", pq_m=4, pq_k=64,
            cluster_capacity=96, term_capacity=48, kmeans_iters=3)
    rows = np.arange(7, 600, 13)
    with jax.default_matmul_precision("bfloat16"):
        mut.add_docs(c.doc_emb[rows], c.doc_tokens[rows])
    delta = np.asarray(mut.delta_segment().doc_planes["codes"])
    base = np.asarray(mut.base.doc_planes["codes"])
    np.testing.assert_array_equal(delta[:len(rows)], base[rows])


# --------------------------------------------------------------------------
# refine semantics
# --------------------------------------------------------------------------

def test_refine_equals_flat_when_width_covers_budget():
    """With R′ ≥ the candidate budget every candidate is exact-rescored,
    so refine-over-pq returns exactly the flat codec's results."""
    c = _corpus(f16_exact=True)
    flat_idx = _build(c, "flat")
    budget = hi.candidate_budget(flat_idx, 4, 4)
    top_r = 25
    mult = -(-budget // top_r)     # ceil: R' = mult*top_r >= budget
    ref_idx = _build(c, f"refine:pq:{mult}")
    qe, qt = jnp.asarray(c.query_emb), jnp.asarray(c.query_tokens)
    a = hi.search(flat_idx, qe, qt, kc=4, k2=4, top_r=top_r)
    b = hi.search(ref_idx, qe, qt, kc=4, k2=4, top_r=top_r)
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.n_candidates),
                                  np.asarray(b.n_candidates))


def test_refine_improves_base_codec_recall():
    from repro.core import metrics
    c = _corpus(n_docs=3000)
    qe, qt = jnp.asarray(c.query_emb), jnp.asarray(c.query_tokens)
    r_pq = hi.search(_build(c, "pq"), qe, qt, kc=4, k2=4, top_r=50)
    r_ref = hi.search(_build(c, "refine:pq:4"), qe, qt, kc=4, k2=4, top_r=50)
    assert (metrics.mrr_at_k(r_ref.doc_ids, c.qrels, 10)
            >= metrics.mrr_at_k(r_pq.doc_ids, c.qrels, 10))


def test_refine_candidate_cost_accounting():
    c = _corpus(n_docs=500)
    idx = _build(c, "refine:pq:4")
    budget = hi.candidate_budget(idx, 4, 4)
    assert hi.candidate_cost(idx, 4, 4, 10) == budget + 40
    plain = _build(c, "pq")
    assert hi.candidate_cost(plain, 4, 4, 10) == budget


# --------------------------------------------------------------------------
# plumbing: bytes accounting, checkpointing
# --------------------------------------------------------------------------

def test_bytes_per_doc_accounting():
    h = 32
    x = jax.random.normal(KEY, (100, h))
    for spec, expect in (("flat", 4 * h), ("sq8", h), ("pq", 4),
                         ("refine:pq:4", 4 + 2 * h)):
        impl = codecs.get(spec)
        params = impl.train(KEY, x, pq_m=4, pq_k=16)
        assert impl.bytes_per_doc(impl.encode(params, x)) == expect, spec


def test_gather_rows_tolerates_pad_ids():
    plane = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    ids = jnp.asarray([[-1, 2], [3, -1]], dtype=jnp.int32)
    rows = codecs_base.gather_rows(plane, ids)
    assert rows.shape == (2, 2, 3)
    np.testing.assert_array_equal(np.asarray(rows[0, 0]),
                                  np.asarray(plane[0]))   # PAD clips to row 0


def test_checkpoint_records_and_validates_codec(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    c = _corpus(n_docs=400)
    idx = _build(c, "sq8")
    path = ckpt.save_index(str(tmp_path), 0, idx)
    assert ckpt.load_manifest(path)["extra"]["codec"] == "sq8"
    restored = ckpt.restore_index(path, idx)
    np.testing.assert_array_equal(np.asarray(restored.doc_planes["codes"]),
                                  np.asarray(idx.doc_planes["codes"]))
    wrong = _build(c, "flat")
    with pytest.raises(ValueError, match="codec"):
        ckpt.restore_index(path, wrong)
