"""Per-kernel validation: shape/dtype sweeps (hypothesis) asserting
allclose against the pure-jnp oracles, in interpret mode on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.assign_topk import ops as at_ops, ref as at_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.pq_adc import ops as adc_ops, ref as adc_ref
from repro.kernels.sq8_dot import ops as sq8_ops, ref as sq8_ref

settings.register_profile("kernels", max_examples=12, deadline=None)
settings.load_profile("kernels")


# --------------------------------------------------------------------------
# pq_adc
# --------------------------------------------------------------------------

@given(b=st.integers(1, 4), c=st.integers(1, 700), m=st.sampled_from([1, 3, 8, 16]),
       k=st.sampled_from([128, 256]))
def test_pq_adc_matches_oracle(b, c, m, k):
    key = jax.random.key(b * 1000 + c)
    lut = jax.random.normal(key, (b, m, k), jnp.float32)
    codes = jax.random.randint(jax.random.fold_in(key, 1), (b, c, m), 0, k)
    out = adc_ops.pq_adc(lut, codes, c_blk=128)
    expect = adc_ref.pq_adc(lut, codes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_pq_adc_paper_scale():
    """The paper's production config: m=96, k=256."""
    key = jax.random.key(0)
    lut = jax.random.normal(key, (2, 96, 256), jnp.float32)
    codes = jax.random.randint(jax.random.fold_in(key, 1), (2, 2048, 96),
                               0, 256)
    out = adc_ops.pq_adc(lut, codes)
    expect = adc_ref.pq_adc(lut, codes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# pq_adc_fused — gather + ADC + mask in one kernel (DESIGN.md §11)
# --------------------------------------------------------------------------

def _fused_case(seed, b, c, m, k, n, code_dtype, mask_row=None,
                dup_ids=False):
    """Random (lut, plane, ids, live) with the edge shapes under test."""
    key = jax.random.key(seed)
    lut = jax.random.normal(key, (b, m, k), jnp.float32)
    plane = jax.random.randint(jax.random.fold_in(key, 1), (n, m),
                               0, k).astype(code_dtype)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (b, c), 0, n,
                             jnp.int32)
    if dup_ids:          # every id appears at least twice per row
        ids = jnp.concatenate([ids[:, : (c + 1) // 2]] * 2, -1)[:, :c]
    live = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.8,
                                (b, c)).astype(jnp.int32)
    if mask_row is not None:
        live = live.at[mask_row % b].set(0)          # fully-masked row
    return lut, plane, ids, live


def _assert_fused_matches_ref(lut, plane, ids, live, c_blk):
    got = np.asarray(adc_ops.pq_adc_fused(lut, plane, ids, live,
                                          c_blk=c_blk))
    want = np.asarray(adc_ref.pq_adc_fused(lut, plane, ids, live))
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


@given(b=st.integers(1, 4), c=st.integers(1, 700),
       m=st.sampled_from([1, 4, 8]), k=st.sampled_from([64, 128, 256]),
       code_i32=st.booleans(), dup=st.booleans(),
       mask_row=st.integers(0, 3))
def test_pq_adc_fused_matches_oracle_on_edge_shapes(b, c, m, k, code_i32,
                                                    dup, mask_row):
    """The ISSUE-6 edge sweep: C not a multiple of c_blk (c_blk=128,
    any C), C smaller than one block (C=1 is a boundary draw),
    duplicate candidate ids, one fully-masked (all -inf) row, and
    uint8 vs int32 code planes — all against ref.py."""
    dtype = jnp.int32 if code_i32 else jnp.uint8
    lut, plane, ids, live = _fused_case(
        b * 7919 + c, b, c, m, k, n=500, code_dtype=dtype,
        mask_row=mask_row, dup_ids=dup)
    _assert_fused_matches_ref(lut, plane, ids, live, c_blk=128)


def test_pq_adc_fused_all_rows_masked_is_all_inf():
    lut, plane, ids, live = _fused_case(0, 3, 200, 4, 64, n=100,
                                        code_dtype=jnp.uint8)
    live = jnp.zeros_like(live)
    out = np.asarray(adc_ops.pq_adc_fused(lut, plane, ids, live, c_blk=128))
    assert np.isneginf(out).all()


def test_pq_adc_fused_never_materializes_candidate_codes():
    """The fused op's whole point: no (B, C, m) — or padded
    (B, C_pad, m) — intermediate may exist anywhere in its jaxpr.  The
    unfused path is the positive control: its gather produces exactly
    that shape, so the walker provably sees such intermediates."""
    b, c, m, k, n, c_blk = 2, 384, 4, 64, 1000, 128
    lut, plane, ids, live = _fused_case(1, b, c, m, k, n=n,
                                        code_dtype=jnp.uint8)

    def shapes_of(fn, *args):
        seen = set()

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if aval is not None and hasattr(aval, "shape"):
                        seen.add(tuple(aval.shape))
                for val in jax.tree_util.tree_leaves(
                        eqn.params, is_leaf=lambda x: hasattr(x, "eqns")):
                    if hasattr(val, "eqns"):
                        walk(val)
                    elif hasattr(val, "jaxpr"):
                        walk(val.jaxpr)
        closed = jax.make_jaxpr(fn)(*args)
        walk(closed.jaxpr)
        return seen

    def is_candidate_codes(shape):
        return (len(shape) == 3 and shape[0] == b and shape[2] == m
                and shape[1] >= c)

    fused_shapes = shapes_of(
        lambda *a: adc_ops.pq_adc_fused(*a, c_blk=c_blk),
        lut, plane, ids, live)
    offenders = sorted(s for s in fused_shapes if is_candidate_codes(s))
    assert not offenders, (
        f"fused kernel materialized candidate codes: {offenders}")

    unfused_shapes = shapes_of(
        lambda l, p, i, lv: jnp.where(lv.astype(bool),
                                      adc_ops.pq_adc(l, p[i]), -jnp.inf),
        lut, plane, ids, live)
    assert any(is_candidate_codes(s) for s in unfused_shapes), (
        "positive control failed: the walker no longer sees the "
        "unfused (B, C, m) gather — fix the walker, not the kernel")


# --------------------------------------------------------------------------
# sq8_dot_fused
# --------------------------------------------------------------------------

@given(b=st.integers(1, 4), c=st.integers(1, 700),
       h=st.sampled_from([16, 32, 64]), mask_row=st.integers(0, 3))
def test_sq8_dot_fused_matches_oracle(b, c, h, mask_row):
    key = jax.random.key(b * 31 + c)
    q = jax.random.normal(key, (b, h), jnp.float32)
    plane = jax.random.randint(jax.random.fold_in(key, 1), (400, h),
                               0, 256).astype(jnp.uint8)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (b, c), 0, 400,
                             jnp.int32)
    live = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.8,
                                (b, c)).astype(jnp.int32)
    live = live.at[mask_row % b].set(0)
    got = np.asarray(sq8_ops.sq8_dot_fused(q, plane, ids, live, c_blk=128))
    want = np.asarray(sq8_ref.sq8_dot_fused(q, plane, ids, live))
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-2)


# --------------------------------------------------------------------------
# fused scorers over block-structured live masks: dead blocks are not
# scored and dead slots not gathered (kernels/row_gather.py)
# --------------------------------------------------------------------------

#: 2 queries × 4 blocks of 128 slots, the last one padded; "lists" of
#: capacity 256 filled from the front, as the served candidate plane is
SKIP_B, SKIP_C, SKIP_BLK, SKIP_LIST, SKIP_N = 2, 500, 128, 256, 400
#: the row every dead slot points at in the "dead_ids_poisoned" case
POISON = SKIP_N - 1
SKIP_CASES = ["all_dead", "list_prefix", "one_slot_per_block",
              "dead_ids_poisoned", "all_live"]


def _skip_case(case):
    """(ids, live) of one case: live ids never name :data:`POISON`."""
    rng = np.random.default_rng(SKIP_CASES.index(case))
    live = np.zeros((SKIP_B, SKIP_C), np.int32)
    if case == "list_prefix":          # live prefix, dead blocks behind
        for row, prefixes in enumerate([(37, 200), (128, 0)]):
            for lst, n in enumerate(prefixes):
                live[row, lst * SKIP_LIST:lst * SKIP_LIST + n] = 1
    elif case == "one_slot_per_block":   # first, middle, last; one dead
        for blk, pos in enumerate((0, SKIP_BLK // 2 - 1, SKIP_BLK - 1)):
            live[:, blk * SKIP_BLK + pos] = 1
    elif case == "dead_ids_poisoned":
        live = (rng.random((SKIP_B, SKIP_C)) < 0.5).astype(np.int32)
    elif case == "all_live":
        live[:] = 1
    ids = rng.integers(0, POISON, (SKIP_B, SKIP_C), dtype=np.int32)
    if case == "dead_ids_poisoned":
        ids = np.where(live != 0, ids, POISON).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(live)


def _assert_dead_lanes_are_inf(got, live):
    live = np.asarray(live) != 0
    np.testing.assert_array_equal(np.isneginf(got), ~live)
    assert np.isfinite(got[live]).all()


@pytest.mark.parametrize("case", SKIP_CASES)
def test_sq8_dot_fused_skips_dead_slots_and_blocks(case):
    """Dead lanes are exactly -inf; live lanes bitwise the unfused
    path's (queries in 1/64 steps keep every sum exact in f32, so any
    order of accumulation agrees) and within the oracle's tolerance."""
    h = 64
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.integers(-64, 65, (SKIP_B, h)) / 64, jnp.float32)
    plane = rng.integers(0, 256, (SKIP_N, h)).astype(np.uint8)
    plane[POISON] = 255
    plane = jnp.asarray(plane)
    ids, live = _skip_case(case)
    got = np.asarray(sq8_ops.sq8_dot_fused(q, plane, ids, live,
                                           c_blk=SKIP_BLK))
    _assert_dead_lanes_are_inf(got, live)
    unfused = np.asarray(sq8_ops.sq8_dot_fused(q, plane, ids, live,
                                               use_kernel=False))
    np.testing.assert_array_equal(got, unfused)
    want = np.asarray(sq8_ref.sq8_dot_fused(q, plane, ids, live))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("case", SKIP_CASES)
def test_pq_adc_fused_skips_dead_slots_and_blocks(case):
    """Dead lanes are exactly -inf; live lanes bitwise the unfused
    ``pq_adc_fragmajor`` kernel's over the gathered codes and within the
    oracle's tolerance."""
    m, k = 8, 256
    rng = np.random.default_rng(11)
    lut = jnp.asarray(rng.standard_normal((SKIP_B, m, k)), jnp.float32)
    plane = rng.integers(0, k, (SKIP_N, m)).astype(np.uint8)
    plane[POISON] = np.argmax(np.asarray(lut)[0], axis=-1)
    plane = jnp.asarray(plane)
    ids, live = _skip_case(case)
    got = np.asarray(adc_ops.pq_adc_fused(lut, plane, ids, live,
                                          c_blk=SKIP_BLK))
    _assert_dead_lanes_are_inf(got, live)
    fragmajor = np.asarray(adc_ops.pq_adc(
        lut, plane[ids].astype(jnp.int32), c_blk=SKIP_BLK))
    lv = np.asarray(live) != 0
    np.testing.assert_array_equal(got[lv], fragmajor[lv])
    want = np.asarray(adc_ref.pq_adc_fused(lut, plane, ids, live))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# assign_topk
# --------------------------------------------------------------------------

@given(n=st.integers(1, 300), l=st.integers(2, 600),
       h=st.sampled_from([16, 32]), k=st.integers(1, 12),
       ties=st.booleans())
def test_topk_scores_matches_lax_topk(n, l, h, k, ties):
    """The dispatch kernel must be BIT-identical to ``lax.top_k`` over
    the plain inner-product plane — scores and ids, including the
    lowest-index-first tie-break (forced by duplicating rows)."""
    k = min(k, l)
    key = jax.random.key(n * 13 + l)
    x = jax.random.normal(key, (n, h), jnp.float32)
    emb = jax.random.normal(jax.random.fold_in(key, 1), (l, h),
                            jnp.float32)
    if ties:             # duplicate the first half: every score tied 2x
        emb = jnp.concatenate([emb[: (l + 1) // 2]] * 2)[:l]
    ws, wi = at_ref.topk_scores(x, emb, k)
    gs, gi = at_ops.topk_scores(x, emb, k, l_blk=128)
    np.testing.assert_array_equal(np.asarray(wi), np.asarray(gi))
    np.testing.assert_allclose(np.asarray(ws), np.asarray(gs),
                               rtol=1e-5, atol=1e-5)

@given(n=st.integers(1, 1200), l=st.integers(2, 600),
       h=st.sampled_from([16, 64, 128]))
def test_assign_argmax_matches_oracle(n, l, h):
    key = jax.random.key(n * 7 + l)
    x = jax.random.normal(key, (n, h), jnp.float32)
    c = jax.random.normal(jax.random.fold_in(key, 1), (l, h), jnp.float32)
    s, i = at_ops.assign_argmax(x, c)
    es, ei = at_ref.assign_argmax(x, c)
    np.testing.assert_allclose(np.asarray(s), np.asarray(es),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))


def test_assign_argmax_is_l2_argmin():
    """⟨x,c⟩ − ½‖c‖² argmax == L2 argmin (the KMeans contract)."""
    key = jax.random.key(3)
    x = jax.random.normal(key, (64, 32))
    c = jax.random.normal(jax.random.fold_in(key, 1), (40, 32))
    _, i = at_ops.assign_argmax(x, c)
    d = np.linalg.norm(np.asarray(x)[:, None] - np.asarray(c)[None], axis=-1)
    np.testing.assert_array_equal(np.asarray(i), d.argmin(axis=1))


# --------------------------------------------------------------------------
# flash_attention
# --------------------------------------------------------------------------

@given(sq=st.sampled_from([64, 200, 256]), sk=st.sampled_from([64, 256, 384]),
       d=st.sampled_from([32, 64]), causal=st.booleans(),
       window=st.sampled_from([0, 32]),
       heads=st.sampled_from([(4, 4), (4, 2), (8, 1)]))
def test_flash_attention_matches_oracle(sq, sk, d, causal, window, heads):
    if causal and sk != sq:
        sk = sq  # causal masks assume aligned positions
    hq, hkv = heads
    key = jax.random.key(sq * 31 + sk)
    q = jax.random.normal(key, (1, hq, sq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, hkv, sk, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, hkv, sk, d))
    out = fa_ops.flash_attention(q, k, v, causal, window, None)
    expect = fa_ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=3e-4, atol=3e-4)


def test_flash_attention_gradient_path():
    key = jax.random.key(9)
    q = jax.random.normal(key, (1, 2, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 32))

    def loss_kernel(q_):
        return fa_ops.flash_attention(q_, k, v, True, 0, None).sum()

    def loss_ref(q_):
        return fa_ref.attention(q_, k, v, causal=True).sum()

    g_k = jax.grad(loss_kernel)(q)
    g_r = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=3e-4, atol=3e-4)


@given(q_chunk=st.sampled_from([64, 128, 256]), causal=st.booleans(),
       window=st.sampled_from([0, 48]))
def test_chunked_attention_matches_dense(q_chunk, causal, window):
    key = jax.random.key(q_chunk)
    q = jax.random.normal(key, (1, 2, 512, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 512, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 512, 32))
    a = fa_ref.attention(q, k, v, causal=causal, window=window)
    b = fa_ref.attention_chunked(q, k, v, causal=causal, window=window,
                                 q_chunk=q_chunk)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)
