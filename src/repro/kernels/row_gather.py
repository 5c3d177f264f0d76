"""In-kernel row gather from an HBM-resident doc plane (DESIGN.md §11).

The fused scorers keep the ``(N, w)`` doc plane in HBM and fetch only
the candidate rows.  A TPU DMA cannot move one row of a tiled HBM
array: a slice must cover whole ``(8, 128)`` tiles, so its row offset
is a multiple of :data:`ROW_GROUP` and its width the plane's full,
128-lane-aligned width (ops.py pads planes that are not).  Each
candidate therefore DMAs the 8-row group that holds it into a
two-slot VMEM buffer — the copy of candidate ``i+1`` is in flight
while candidate ``i`` is extracted — and the wanted row is selected
out of the group with a sublane mask and written, widened to int32,
into row ``i`` of the ``(c_blk, w)`` output scratch.

Candidate ids arrive one ``(1, 1, c_blk)`` block per grid step in SMEM
(a per-step block, not a whole-call scalar prefetch, so SMEM use is
independent of the batch and the candidate budget).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows per DMA: the sublane tile of the plane's HBM layout
ROW_GROUP = 8


def scratch_shapes(c_blk: int, width: int, dtype) -> list:
    """VMEM/semaphore scratch for :func:`gather_rows` over a plane of
    ``width`` lanes and storage ``dtype``."""
    return [pltpu.VMEM((2, ROW_GROUP, width), dtype),
            pltpu.VMEM((c_blk, width), jnp.int32),
            pltpu.SemaphoreType.DMA((2,))]


def gather_rows(ids_ref, plane_ref, groups_sc, rows_sc, sems, c_blk: int):
    """``rows_sc[i] = plane[ids[i]]`` (int32) for the step's ``c_blk``
    candidate ids; ``plane_ref`` stays in HBM (``memory_space=ANY``)."""
    def group_copy(i, slot):
        start = pl.multiple_of(ids_ref[0, 0, i] // ROW_GROUP * ROW_GROUP,
                               ROW_GROUP)
        return pltpu.make_async_copy(
            plane_ref.at[pl.ds(start, ROW_GROUP)], groups_sc.at[slot],
            sems.at[slot])

    sublane = jax.lax.broadcasted_iota(
        jnp.int32, (ROW_GROUP, rows_sc.shape[1]), 0)
    group_copy(0, 0).start()

    def body(i, _):
        slot = i % 2

        @pl.when(i + 1 < c_blk)
        def _prefetch():
            group_copy(i + 1, 1 - slot).start()

        group_copy(i, slot).wait()
        group = groups_sc[slot].astype(jnp.int32)          # (8, w)
        pick = sublane == ids_ref[0, 0, i] % ROW_GROUP
        rows_sc[pl.ds(i, 1), :] = jnp.sum(jnp.where(pick, group, 0),
                                          axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, c_blk, body, 0)


def pad_plane(plane: jax.Array) -> jax.Array:
    """Pad a plane to whole ``(ROW_GROUP, 128)`` tiles (a no-op for the
    aligned planes of real corpora; rows and lanes added are never
    selected)."""
    n, w = plane.shape
    pad_n, pad_w = (-n) % ROW_GROUP, (-w) % 128
    if pad_n or pad_w:
        plane = jnp.pad(plane, ((0, pad_n), (0, pad_w)))
    return plane
