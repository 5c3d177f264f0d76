"""Public jit'd wrapper for the PQ ADC kernel.

Handles layout (candidate-major → fragment-major), padding C to the tile
size and the codes plane to whole tiles, and the CPU/TPU switch
(:func:`repro.kernels.interpret_mode`): on the CPU backend the
pallas_call runs in ``interpret=True`` mode (the kernel body executed by
XLA:CPU) so the same code path is exercised everywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, row_gather
from repro.kernels.pq_adc import kernel, ref


@functools.partial(jax.jit, static_argnames=("c_blk", "use_kernel"))
def pq_adc(lut: jax.Array, codes: jax.Array, *, c_blk: int = 512,
           use_kernel: bool = True) -> jax.Array:
    """lut: (B, m, k) f32; codes: (B, C, m) i32 → scores (B, C) f32."""
    if not use_kernel:
        return ref.pq_adc(lut, codes)
    b, c, m = codes.shape
    pad = (-c) % c_blk
    codes_fm = jnp.swapaxes(codes, 1, 2)                     # (B, m, C)
    if pad:
        codes_fm = jnp.pad(codes_fm, ((0, 0), (0, 0), (0, pad)))
    out = kernel.pq_adc_fragmajor(lut, codes_fm, c_blk=c_blk,
                                  interpret=interpret_mode())
    return out[:, :c]


@functools.partial(jax.jit, static_argnames=("c_blk", "use_kernel"))
def pq_adc_fused(lut: jax.Array, codes_plane: jax.Array, ids: jax.Array,
                 live: jax.Array, *, c_blk: int = 256,
                 use_kernel: bool = True) -> jax.Array:
    """Fused gather + ADC + mask over the *resident* codes plane.

    lut: (B, m, k) f32; codes_plane: (N, m) uint8/i32; ids: (B, C) i32
    in [0, N); live: (B, C) bool/i32 (falsy = masked) → (B, C) f32
    scores with ``-inf`` on masked lanes.  The candidate rows are
    gathered inside the kernel (DMA from the HBM-resident plane) — no
    (B, C, m) intermediate is ever allocated.

    Padding done here so the kernel sees aligned shapes only:
      · ids of dead slots → ``row_gather.DEAD``, which the kernel
        never gathers; live ids clipped into ``[0, N)``;
      · C → multiple of ``c_blk`` with dead slots (stripped after the
        call);
      · k → multiple of 128 with zero LUT columns (codes < k never
        select them);
      · the plane → whole (8, 128) tiles (:func:`row_gather.pad_plane`;
        a no-op for 128-lane planes of 8k rows, a per-call copy for
        narrower ones such as m=96).
    """
    if not use_kernel:
        return ref.pq_adc_fused(lut, codes_plane, ids, live)
    b, m, k = lut.shape
    _, c = ids.shape
    k_pad = (-k) % 128
    if k_pad:
        lut = jnp.pad(lut, ((0, 0), (0, 0), (0, k_pad)))
    c_pad = (-c) % c_blk
    ids = row_gather.live_ids(ids, live, codes_plane.shape[0])
    live = live.astype(jnp.int32)
    if c_pad:
        ids = jnp.pad(ids, ((0, 0), (0, c_pad)),
                      constant_values=row_gather.DEAD)
        live = jnp.pad(live, ((0, 0), (0, c_pad)))
    out = kernel.pq_adc_fused(lut, row_gather.pad_plane(codes_plane), ids,
                              live, c_blk=c_blk,
                              interpret=interpret_mode())
    return out[:, :c]
