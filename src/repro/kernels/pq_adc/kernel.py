"""PQ ADC scoring kernel (paper Eq. 4) — TPU-native design.

Problem: per query, a (m, k) inner-product LUT is known; each candidate
document is m uint8/int32 codes; its score is Σ_j lut[j, code_j].

GPU/Faiss does this with SIMD gathers through L1.  TPUs have no fast
per-lane gather from VMEM, so we *reformulate the gather as a one-hot
selection* on the VPU, candidates on lanes:

    score(c) = Σ_j  Σ_i [code_cj == i] · lut[j, i]      (exact: one hit)

Layout: codes arrive **fragment-major** ``(B, m, C)`` and the LUT
transposed to ``(B, k, m)``, so one fragment's codes are a lane row and
one fragment's LUT column broadcasts across lanes; the one-hot plane
per fragment is (k, C_blk).  Scores leave through a ``(B, 1, C)`` plane
whose ``(1, 1, C_blk)`` blocks satisfy the TPU's (8, 128) block rule.

Grid: (B, C / C_blk); the LUT block (1, k, m) is revisited across the
candidate dimension so it stays resident in VMEM for the whole query.

VMEM budget per grid step (m=96, k=256, C_blk=512):
    lut 96·256·4 = 98 KiB, codes 96·512·4 = 196 KiB,
    onehot 256·512·4 = 512 KiB, out 2 KiB   → ≈ 0.8 MiB ≪ 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import row_gather


def _adc_accumulate(codes_t, lut_t, m: int, k: int, c_blk: int):
    """Σ_j lut[j, codes[j, c]] for each candidate lane c.

    ``codes_t``: (≥m, c_blk) i32, candidates on lanes; ``lut_t``: (k, m)
    f32.  Fragment by fragment, the one-hot selects the LUT column on
    the VPU and a sublane sum extracts it — exact (one nonzero term per
    lane), so accumulation order is j = 0..m-1 exactly as in the
    unfused reference formulation."""
    kio = jax.lax.broadcasted_iota(jnp.int32, (k, c_blk), 0)
    acc = jnp.zeros((1, c_blk), jnp.float32)
    for j in range(m):        # static unroll — m ≤ 96
        hit = codes_t[j:j + 1, :] == kio                       # (k, c_blk)
        acc = acc + jnp.sum(jnp.where(hit, lut_t[:, j:j + 1], 0.0),
                            axis=0, keepdims=True)
    return acc


def _adc_kernel(lut_ref, codes_ref, out_ref, *, m: int, k: int, c_blk: int):
    out_ref[0] = _adc_accumulate(codes_ref[0], lut_ref[0], m, k, c_blk)


@functools.partial(jax.jit, static_argnames=("c_blk", "interpret"))
def pq_adc_fragmajor(lut: jax.Array, codes_fm: jax.Array, *,
                     c_blk: int = 512, interpret: bool = False) -> jax.Array:
    """lut: (B, m, k) f32; codes_fm: (B, m, C) i32 → scores (B, C) f32.

    C must be a multiple of ``c_blk`` (ops.py pads); k a multiple of 128.
    Scores leave through a (B, 1, C) plane: a (1, 1, c_blk) block is
    tile-legal where a (1, c_blk) block of (B, C) is not.
    """
    b, m, k = lut.shape
    _, _, c = codes_fm.shape
    assert c % c_blk == 0, (c, c_blk)
    out = pl.pallas_call(
        functools.partial(_adc_kernel, m=m, k=k, c_blk=c_blk),
        grid=(b, c // c_blk),
        in_specs=[
            pl.BlockSpec((1, k, m), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, m, c_blk), lambda bi, ci: (bi, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        interpret=interpret,
        name="pq_adc_fragmajor",
    )(jnp.swapaxes(lut, 1, 2), codes_fm)
    return out.reshape(b, c)


# --------------------------------------------------------------------------
# fused gather + ADC (PR 6) — the whole within-list evaluation in one kernel
# --------------------------------------------------------------------------
#
# The unfused path above needs the caller to materialize the (B, C, m)
# candidate-code gather in HBM first (an XLA gather over the resident
# (N, m) plane), then streams that plane back through the ADC kernel —
# 2× the HBM traffic of the codes actually scored, plus the intermediate
# itself.  The fused kernel takes the *resident* plane and the (B, C)
# candidate ids and performs the row gather inside the kernel body
# (:mod:`repro.kernels.row_gather`):
#
#   · each grid step's c_blk candidate ids arrive in SMEM, so every
#     row's HBM address is known to the scalar core;
#   · the codes plane stays in HBM (memory_space=ANY); each live
#     candidate's 8-row tile group is DMA'd into VMEM, double-buffered,
#     and its row extracted into a (c_blk, w) int32 tile; dead slots
#     are not gathered, and a block with no live slot is neither
#     gathered nor scored;
#   · the live mask (dedup ∧ ¬tombstone ∧ namespace) is applied
#     in-kernel: masked lanes leave as -inf, so the (B, C) score plane
#     that reaches HBM is already selection-ready.
#
# Nothing of shape (B, C, m) ever exists — asserted over the jaxpr by
# tests/test_kernels.py.  Per-candidate accumulation order (fragment
# j = 0..m-1, :func:`_adc_accumulate`) is identical to `_adc_kernel`,
# so fused and unfused *kernel* scores agree bitwise; only the pure-jnp
# oracle's m-reduction order differs (DESIGN.md §11 bounds it).


def _adc_fused_kernel(ids_ref, count_ref, lut_ref, live_ref, plane_ref,
                      out_ref, *scratch, m: int, k: int, c_blk: int):
    def score_rows(rows):                              # (c_blk, w) i32
        return _adc_accumulate(rows.T, lut_ref[0], m, k, c_blk)

    row_gather.masked_scores(score_rows, ids_ref, count_ref, live_ref,
                             plane_ref, out_ref, *scratch, c_blk=c_blk)


@functools.partial(jax.jit, static_argnames=("c_blk", "interpret"))
def pq_adc_fused(lut: jax.Array, codes_plane: jax.Array, ids: jax.Array,
                 live: jax.Array, *, c_blk: int = 256,
                 interpret: bool = False) -> jax.Array:
    """lut: (B, m, k) f32; codes_plane: (N, w) int, N % 8 == 0,
    w % 128 == 0, w ≥ m; ids: (B, C) i32 in [0, N) on live slots,
    ``row_gather.DEAD`` (never gathered) on dead ones; live: (B, C) i32
    (0 = masked) → scores (B, C) f32, ``-inf`` on masked lanes.

    C must be a multiple of ``c_blk`` and k of 128 (ops.py pads all of
    these).  The codes plane keeps its storage dtype (uint8 when
    k ≤ 256) all the way into VMEM; widening to i32 happens on-chip.
    """
    b, m, k = lut.shape
    _, c = ids.shape
    assert c % c_blk == 0, (c, c_blk)
    n_blk = c // c_blk
    out = pl.pallas_call(
        functools.partial(_adc_fused_kernel, m=m, k=k, c_blk=c_blk),
        grid=(b, n_blk),
        in_specs=[
            row_gather.ids_spec(c_blk, n_blk),
            row_gather.count_spec(n_blk),
            pl.BlockSpec((1, k, m), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec(memory_space=pl.ANY),         # resident plane
        ],
        out_specs=pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        scratch_shapes=row_gather.scratch_shapes(
            c_blk, codes_plane.shape[1], codes_plane.dtype),
        interpret=interpret,
        name="pq_adc_fused",
    )(ids.reshape(b * n_blk, 1, c_blk), row_gather.block_counts(live, c_blk),
      jnp.swapaxes(lut, 1, 2), live.reshape(b, 1, c), codes_plane)
    return out.reshape(b, c)
