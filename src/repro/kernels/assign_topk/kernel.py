"""Fused embedding×centroid scoring with running argmax — the KMeans
assignment / cluster-dispatch hot spot (paper Eq. 6, DESIGN.md §2).

Faiss scans centroids with a CPU heap; on TPU the score plane is an MXU
matmul tiled so the (N_blk, L_blk) tile lives in VMEM, with a *running*
max/argmax folded across centroid tiles — the full (N, L) plane never
reaches HBM.  The centroid ``-½‖c‖²`` bias (inner-product ↔ L2 argmin
equivalence) is computed in-kernel per tile.

Grid: (N/N_blk, L/L_blk), centroid axis innermost; the output blocks are
indexed by the N tile only, so they are *revisited* across centroid
tiles — the legal sequential-reduction pattern on TPU grids.

VMEM per step (N_blk=256, L_blk=512, h=128):
    x 128 KiB + c 256 KiB + tile 512 KiB + outs 2 KiB ≈ 0.9 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, best_s_ref, best_i_ref, *, l_blk: int):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)            # (n_blk, h)
    c = c_ref[...].astype(jnp.float32)            # (l_blk, h)
    c_norm = 0.5 * jnp.sum(c * c, axis=-1)        # (l_blk,)
    s = jnp.dot(x, c.T, preferred_element_type=jnp.float32) - c_norm[None, :]
    local_s = jnp.max(s, axis=-1, keepdims=True)          # (n_blk, 1)
    local_i = (jnp.argmax(s, axis=-1, keepdims=True).astype(jnp.int32)
               + j * l_blk)

    @pl.when(j == 0)
    def _init():
        best_s_ref[...] = local_s
        best_i_ref[...] = local_i

    @pl.when(j > 0)
    def _merge():
        prev_s = best_s_ref[...]
        take = local_s > prev_s
        best_s_ref[...] = jnp.where(take, local_s, prev_s)
        best_i_ref[...] = jnp.where(take, local_i, best_i_ref[...])


# --------------------------------------------------------------------------
# running top-k (PR 6) — the dispatch stage's cluster selection
# --------------------------------------------------------------------------
#
# Same tiling as the argmax kernel, but the per-query state carried
# across centroid tiles is a (k,) best-list instead of a scalar.  Each
# tile concatenates [previous best ‖ tile scores] and re-selects top-k
# with *first-position* tie-break: previous winners come from earlier
# tiles (smaller global indices) and sit first in the concat, and
# within a tile the column iota ascends — so the selection reproduces
# ``lax.top_k``'s lowest-index-first tie-break exactly, by induction.
# Padded centroid columns are masked to -inf via the static ``l_true``
# (duplicate-row padding is safe for argmax but NOT for top-k: a
# duplicate would enter the best list as a second distinct id).
#
# Unlike dispatch scoring via assign_argmax, this op uses the *plain*
# inner product — no -½‖c‖² bias — matching cluster_selector's routing
# score (the bias is a KMeans-assignment L2 equivalence, not a routing
# quantity).


def _select_topk(s, ids, k: int):
    """Static-k selection of (n, w) rows; first position wins ties."""
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    out_s, out_i = [], []
    for _ in range(k):
        best = jnp.max(s, axis=-1)
        p = jnp.argmax(s, axis=-1)              # first max position
        sel = pos == p[:, None]
        out_s.append(best)
        out_i.append(jnp.sum(jnp.where(sel, ids, 0), axis=-1))
        s = jnp.where(sel, -jnp.inf, s)
    return jnp.stack(out_s, axis=-1), jnp.stack(out_i, axis=-1)


def _topk_kernel(x_ref, e_ref, best_s_ref, best_i_ref, *, k: int,
                 l_blk: int, l_true: int):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)            # (n_blk, h)
    e = e_ref[...].astype(jnp.float32)            # (l_blk, h)
    s = jnp.dot(x, e.T, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * l_blk
    s = jnp.where(col < l_true, s, -jnp.inf)      # mask padded columns

    @pl.when(j == 0)
    def _init():
        ts, ti = _select_topk(s, col, k)
        best_s_ref[...] = ts
        best_i_ref[...] = ti

    @pl.when(j > 0)
    def _merge():
        cs = jnp.concatenate([best_s_ref[...], s], axis=-1)
        ci = jnp.concatenate([best_i_ref[...], col], axis=-1)
        ts, ti = _select_topk(cs, ci, k)
        best_s_ref[...] = ts
        best_i_ref[...] = ti


@functools.partial(jax.jit,
                   static_argnames=("k", "n_blk", "l_blk", "l_true",
                                    "interpret"))
def topk_scores(x: jax.Array, emb: jax.Array, *, k: int, n_blk: int = 256,
                l_blk: int = 512, l_true: int, interpret: bool = False
                ) -> tuple[jax.Array, jax.Array]:
    """x: (N, h); emb: (L, h) → (scores (N, k), idx (N, k)) — the top-k
    plain inner products per row, ``lax.top_k`` tie-break semantics.

    N % n_blk == 0 and L % l_blk == 0 (ops.py pads); columns ≥
    ``l_true`` are padding and are masked to -inf in-kernel.
    """
    n, h = x.shape
    l, _ = emb.shape
    assert n % n_blk == 0 and l % l_blk == 0, (n, n_blk, l, l_blk)
    assert k <= l_true <= l, (k, l_true, l)
    grid = (n // n_blk, l // l_blk)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k, l_blk=l_blk, l_true=l_true),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_blk, h), lambda i, j: (i, 0)),
            pl.BlockSpec((l_blk, h), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_blk, k), lambda i, j: (i, 0)),
            pl.BlockSpec((n_blk, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((n, k), jnp.int32),
        ],
        interpret=interpret,
        name="topk_scores",
    )(x, emb)


@functools.partial(jax.jit, static_argnames=("n_blk", "l_blk", "interpret"))
def assign_argmax(x: jax.Array, centroids: jax.Array, *, n_blk: int = 256,
                  l_blk: int = 512, interpret: bool = False
                  ) -> tuple[jax.Array, jax.Array]:
    """x: (N, h); centroids: (L, h) → (best_score (N,), best_idx (N,)).

    argmax_j ⟨x, c_j⟩ − ½‖c_j‖²  ==  argmin_j ‖x − c_j‖².
    N % n_blk == 0 and L % l_blk == 0 (ops.py pads).  The outputs leave
    as (N, 1) columns: a TPU block of a 1-D array must match its
    (1024-element) HBM tiling, an (n_blk, 1) block of a column need not.
    """
    n, h = x.shape
    l, _ = centroids.shape
    assert n % n_blk == 0 and l % l_blk == 0, (n, n_blk, l, l_blk)
    grid = (n // n_blk, l // l_blk)
    s, i = pl.pallas_call(
        functools.partial(_assign_kernel, l_blk=l_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_blk, h), lambda i, j: (i, 0)),
            pl.BlockSpec((l_blk, h), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_blk, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((n_blk, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
        name="assign_argmax",
    )(x, centroids)
    return s[:, 0], i[:, 0]
