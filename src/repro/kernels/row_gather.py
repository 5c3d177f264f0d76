"""In-kernel row gather from an HBM-resident doc plane (DESIGN.md §11).

The fused scorers keep the ``(N, w)`` doc plane in HBM and fetch only
the rows of live candidates.  A TPU DMA cannot move one row of a tiled
HBM array: a slice must cover whole ``(8, 128)`` tiles, so its row
offset is a multiple of :data:`ROW_GROUP` and its width the plane's
full, 128-lane-aligned width (ops.py pads planes that are not).  Each
live candidate therefore DMAs the 8-row group that holds it into a
two-slot VMEM buffer — the copy of the next live candidate is in
flight while the current one is extracted — and the wanted row is
selected out of the group with a sublane mask and written, widened to
int32, into its own slot's row of the ``(c_blk, w)`` output scratch.

The scorer does no work for a slot the live mask has already decided,
at two levels:

* **dead blocks** — :func:`block_counts` reduces the ``(B, C)`` live
  mask to one count per ``(query, c_blk block)``; a grid step whose
  count is 0 writes ``-inf`` and gathers and scores nothing
  (:func:`masked_scores`);
* **dead slots** — :func:`live_ids` puts the sentinel ``-1`` in place
  of the id of every dead slot.  :func:`gather_rows` first walks the
  block's ids on the scalar core and packs the positions of the live
  ones into an SMEM scratch, then runs the DMA pipeline over those
  only.  Rows of dead slots keep whatever the scratch held: finite
  int32 values whose output lanes are independent of the live ones
  and leave as ``-inf``, so live lanes are bitwise what a gather of
  every slot gives.

Candidate ids and block counts arrive one block per grid step in SMEM
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

#: the id of a dead slot (:func:`live_ids`); never gathered
DEAD = -1

#: ids the packing walk reads per loop trip (Mosaic unrolls a loop
#: fully or not at all, and a full unroll of c_blk trips makes the CPU
#: interpreter's compile 30× slower); c_blk is a multiple of 128
PACK_UNROLL = 8


def live_ids(ids: jax.Array, live: jax.Array, n_rows: int) -> jax.Array:
    """(B, C) int32 ids in ``[0, n_rows)`` where ``live``, :data:`DEAD`
    elsewhere."""
    return jnp.where(live != 0,
                     jnp.clip(ids.astype(jnp.int32), 0, n_rows - 1), DEAD)


def block_counts(live: jax.Array, c_blk: int) -> jax.Array:
    """Live slots per ``(query, c_blk block)`` of a (B, C) mask, as the
    (B·n_blk, 1, 1) int32 plane whose (1, 1, 1) blocks
    :func:`count_spec` hands one grid step each."""
    b, c = live.shape
    return (live != 0).reshape(b * (c // c_blk), 1, c_blk).sum(
        -1, keepdims=True, dtype=jnp.int32)


def ids_spec(c_blk: int, n_blk: int) -> pl.BlockSpec:
    """The step's ``c_blk`` ids of a (B·n_blk, 1, c_blk) plane, in SMEM."""
    return pl.BlockSpec((1, 1, c_blk), lambda bi, ci: (bi * n_blk + ci, 0, 0),
                        memory_space=pltpu.SMEM)


def count_spec(n_blk: int) -> pl.BlockSpec:
    """The step's live count of :func:`block_counts`, in SMEM."""
    return pl.BlockSpec((1, 1, 1), lambda bi, ci: (bi * n_blk + ci, 0, 0),
                        memory_space=pltpu.SMEM)


def scratch_shapes(c_blk: int, width: int, dtype) -> list:
    """VMEM/SMEM/semaphore scratch for :func:`masked_scores` over a plane
    of ``width`` lanes and storage ``dtype``."""
    return [pltpu.VMEM((2, ROW_GROUP, width), dtype),
            pltpu.VMEM((c_blk, width), jnp.int32),
            pltpu.SMEM((c_blk,), jnp.int32),
            pltpu.SemaphoreType.DMA((2,))]


def gather_rows(ids_ref, plane_ref, groups_sc, rows_sc, pos_sc, sems,
                c_blk: int):
    """``rows_sc[i] = plane[ids[i]]`` (int32) for each of the step's
    ``c_blk`` ids that is not :data:`DEAD`; ``plane_ref`` stays in HBM
    (``memory_space=ANY``)."""
    def pack(g, n):
        for u in range(PACK_UNROLL):
            i = g * PACK_UNROLL + u
            pos_sc[n] = i            # kept only if slot i is live
            n = n + (ids_ref[0, 0, i] != DEAD).astype(jnp.int32)
        return n

    n_live = jax.lax.fori_loop(0, c_blk // PACK_UNROLL, pack, jnp.int32(0))

    def group_copy(j, slot):
        row = ids_ref[0, 0, pos_sc[j]]
        start = pl.multiple_of(row // ROW_GROUP * ROW_GROUP, ROW_GROUP)
        return pltpu.make_async_copy(
            plane_ref.at[pl.ds(start, ROW_GROUP)], groups_sc.at[slot],
            sems.at[slot])

    sublane = jax.lax.broadcasted_iota(
        jnp.int32, (ROW_GROUP, rows_sc.shape[1]), 0)

    @pl.when(n_live > 0)
    def _first():
        group_copy(0, 0).start()

    def body(j, _):
        slot = j % 2

        @pl.when(j + 1 < n_live)
        def _prefetch():
            group_copy(j + 1, 1 - slot).start()

        group_copy(j, slot).wait()
        pos = pos_sc[j]
        group = groups_sc[slot].astype(jnp.int32)          # (8, w)
        pick = sublane == ids_ref[0, 0, pos] % ROW_GROUP
        rows_sc[pl.ds(pos, 1), :] = jnp.sum(jnp.where(pick, group, 0),
                                            axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, n_live, body, 0)


def masked_scores(score_rows, ids_ref, count_ref, live_ref, plane_ref,
                  out_ref, groups_sc, rows_sc, pos_sc, sems, c_blk: int):
    """One grid step of a fused scorer: ``out = score_rows(rows)`` on
    live lanes, ``-inf`` elsewhere; a block with no live slot writes
    ``-inf`` and does nothing else.  ``score_rows`` maps the gathered
    (c_blk, w) int32 rows to (1, c_blk) f32 scores, lane by lane."""
    n_live = count_ref[0, 0, 0]

    @pl.when(n_live == 0)
    def _dead():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    @pl.when(n_live > 0)
    def _live():
        gather_rows(ids_ref, plane_ref, groups_sc, rows_sc, pos_sc, sems,
                    c_blk)
        out_ref[0] = jnp.where(live_ref[0] != 0, score_rows(rows_sc[...]),
                               -jnp.inf)


def pad_plane(plane: jax.Array) -> jax.Array:
    """Pad a plane to whole ``(ROW_GROUP, 128)`` tiles (a no-op for the
    aligned planes of real corpora; rows and lanes added are never
    selected)."""
    n, w = plane.shape
    pad_n, pad_w = (-n) % ROW_GROUP, (-w) % 128
    if pad_n or pad_w:
        plane = jnp.pad(plane, ((0, pad_n), (0, pad_w)))
    return plane
