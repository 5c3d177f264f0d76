"""Public jit'd wrapper for the fused SQ8 gather+dot kernel: pads C to
the tile size and the plane to whole tiles, clips live ids defensively
and marks dead ones (:func:`row_gather.live_ids`), and takes the
compile-or-interpret decision of
:func:`repro.kernels.interpret_mode` so CPU CI runs the same kernel
body."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, row_gather
from repro.kernels.sq8_dot import kernel, ref


@functools.partial(jax.jit, static_argnames=("c_blk", "use_kernel"))
def sq8_dot_fused(q_scaled: jax.Array, codes_plane: jax.Array,
                  ids: jax.Array, live: jax.Array, *, c_blk: int = 256,
                  use_kernel: bool = True) -> jax.Array:
    """Fused gather + dequantized dot + mask over the resident plane.

    q_scaled: (B, h) f32 (queries already multiplied by the per-dim
    scale); codes_plane: (N, h) u8; ids: (B, C); live: (B, C) → (B, C)
    f32 *bias-free* scores, ``-inf`` on masked lanes.  The caller adds
    the per-query ⟨q, lo⟩ bias afterwards (-inf survives the add).
    """
    if not use_kernel:
        return ref.sq8_dot_fused(q_scaled, codes_plane, ids, live)
    _, c = ids.shape
    c_pad = (-c) % c_blk
    ids = row_gather.live_ids(ids, live, codes_plane.shape[0])
    live = live.astype(jnp.int32)
    if c_pad:
        ids = jnp.pad(ids, ((0, 0), (0, c_pad)),
                      constant_values=row_gather.DEAD)
        live = jnp.pad(live, ((0, 0), (0, c_pad)))
    out = kernel.sq8_dot_fused(q_scaled, row_gather.pad_plane(codes_plane),
                               ids, live, c_blk=c_blk,
                               interpret=interpret_mode())
    return out[:, :c]
