"""Public wrapper: pads N/L to tile multiples, strips the padding, and
takes the compile-or-interpret decision of
:func:`repro.kernels.interpret_mode`."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.assign_topk import kernel, ref


@functools.partial(jax.jit, static_argnames=("n_blk", "l_blk", "use_kernel"))
def assign_argmax(x: jax.Array, centroids: jax.Array, *, n_blk: int = 256,
                  l_blk: int = 512, use_kernel: bool = True
                  ) -> tuple[jax.Array, jax.Array]:
    if not use_kernel:
        return ref.assign_argmax(x, centroids)
    n, h = x.shape
    l = centroids.shape[0]
    n_blk = min(n_blk, max(8, n))
    l_blk = min(l_blk, max(8, l))
    pad_n = (-n) % n_blk
    pad_l = (-l) % l_blk
    xp = jnp.pad(x, ((0, pad_n), (0, 0)))
    # pad centroids with COPIES of centroid 0: duplicates can only tie,
    # and the running-max merge breaks ties toward the earlier tile, so
    # the original index always wins. (A huge-norm sentinel was tried
    # first and refuted by hypothesis: x·c − ‖c‖²/2 = inf − inf = NaN.)
    cp = (jnp.concatenate(
        [centroids, jnp.broadcast_to(centroids[:1], (pad_l, h))])
        if pad_l else centroids)
    s, i = kernel.assign_argmax(xp, cp, n_blk=n_blk, l_blk=l_blk,
                                interpret=interpret_mode())
    return s[:n], i[:n]


@functools.partial(jax.jit,
                   static_argnames=("k", "n_blk", "l_blk", "use_kernel"))
def topk_scores(x: jax.Array, emb: jax.Array, k: int, *, n_blk: int = 256,
                l_blk: int = 512, use_kernel: bool = True
                ) -> tuple[jax.Array, jax.Array]:
    """Top-k plain inner products per row of ``x`` against ``emb``,
    ``lax.top_k`` semantics (score desc, lowest index first on ties).

    Padding uses zero rows masked to -inf in-kernel via the static
    ``l_true`` — NOT the duplicate-row trick from assign_argmax, which
    is only safe for argmax (a duplicated centroid would enter a top-k
    list twice under a second id).
    """
    if not use_kernel:
        return ref.topk_scores(x, emb, k)
    n, h = x.shape
    l = emb.shape[0]
    assert k <= l, (k, l)
    n_blk = min(n_blk, max(8, n))
    l_blk = min(l_blk, max(8, l))
    pad_n = (-n) % n_blk
    pad_l = (-l) % l_blk
    xp = jnp.pad(x, ((0, pad_n), (0, 0)))
    ep = jnp.pad(emb, ((0, pad_l), (0, 0)))
    s, i = kernel.topk_scores(xp, ep, k=k, n_blk=n_blk, l_blk=l_blk,
                              l_true=l, interpret=interpret_mode())
    return s[:n], i[:n]
