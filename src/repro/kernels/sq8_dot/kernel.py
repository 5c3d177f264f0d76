"""Fused gather + dequantized dot for the SQ8 codec (DESIGN.md §11).

SQ8 scoring is ⟨q·scale, code⟩ + ⟨q, lo⟩: a pre-scaled dot over the
gathered byte rows plus a per-query bias.  The unfused path gathers the
(B, C, h) byte rows in HBM first; this kernel keeps the (N, h) codes
plane resident in HBM and DMAs the rows of live candidates straight
into VMEM — the shared :mod:`repro.kernels.row_gather` as in
``pq_adc/kernel._adc_fused_kernel``, with the one-hot ADC loop replaced
by a single (1, h)·(c_blk, h)ᵀ MXU dot at full f32 precision.  A block
with no live candidate is neither gathered nor scored.

The live mask is applied in-kernel (-inf); the per-query bias is added
*outside* by the caller after masking (-inf + bias = -inf, so masked
lanes stay -inf) — keeping the kernel bias-free means the mask needs no
special-casing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import row_gather


def _sq8_fused_kernel(ids_ref, count_ref, q_ref, live_ref, plane_ref,
                      out_ref, *scratch, c_blk: int):
    def score_rows(rows):                              # (c_blk, h) i32
        return jax.lax.dot_general(                    # (1, c_blk)
            q_ref[0], rows.astype(jnp.float32),        # q pre-scaled f32
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    row_gather.masked_scores(score_rows, ids_ref, count_ref, live_ref,
                             plane_ref, out_ref, *scratch, c_blk=c_blk)


@functools.partial(jax.jit, static_argnames=("c_blk", "interpret"))
def sq8_dot_fused(q_scaled: jax.Array, codes_plane: jax.Array,
                  ids: jax.Array, live: jax.Array, *, c_blk: int = 256,
                  interpret: bool = False) -> jax.Array:
    """q_scaled: (B, h) f32; codes_plane: (N, w) u8, N % 8 == 0,
    w % 128 == 0, w ≥ h (zero-padded lanes); ids: (B, C) i32 in
    [0, N) on live slots, ``row_gather.DEAD`` (never gathered) on dead
    ones; live: (B, C) i32 → (B, C) f32 bias-free scores, ``-inf`` on
    masked lanes.  C must be a multiple of ``c_blk`` (ops.py pads)."""
    b, h = q_scaled.shape
    _, c = ids.shape
    w = codes_plane.shape[1]
    assert c % c_blk == 0, (c, c_blk)
    n_blk = c // c_blk
    if w != h:
        q_scaled = jnp.pad(q_scaled, ((0, 0), (0, w - h)))
    out = pl.pallas_call(
        functools.partial(_sq8_fused_kernel, c_blk=c_blk),
        grid=(b, n_blk),
        in_specs=[
            row_gather.ids_spec(c_blk, n_blk),
            row_gather.count_spec(n_blk),
            pl.BlockSpec((1, 1, w), lambda bi, ci: (bi, 0, 0)),
            pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi, 0, ci)),
            pl.BlockSpec(memory_space=pl.ANY),         # resident plane
        ],
        out_specs=pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        scratch_shapes=row_gather.scratch_shapes(c_blk, w,
                                                 codes_plane.dtype),
        interpret=interpret,
        name="sq8_dot_fused",
    )(ids.reshape(b * n_blk, 1, c_blk), row_gather.block_counts(live, c_blk),
      q_scaled.reshape(b, 1, w), live.reshape(b, 1, c), codes_plane)
    return out.reshape(b, c)
