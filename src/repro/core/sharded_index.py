"""Document-sharded HI² — the index-parallel serving path (DESIGN.md §6).

A single-device :class:`~repro.core.hybrid_index.HybridIndex` caps the
corpus at one device's HBM.  This module splits the *documents* (and
with them the codec doc planes, the namespace plane and the
inverted-list entries) over a device mesh and runs the SAME staged
query-execution engine as every other variant
(:mod:`repro.core.exec`, DESIGN.md §9) per shard under ``shard_map``:

    shard s owns the contiguous doc range [s·P, (s+1)·P)

    replicated per device : cluster/term selectors, codec params, queries
    sharded (leading axis) : every codec doc plane, ``doc_ns``, the
                             list entry planes filtered to the shard's
                             docs, and (for sparse-built indexes) the
                             BM25 impact plane split by the same
                             permutation

    per shard : dispatch → gather → dedup → filter → score → local top-R′
    merge     : all-gather of the (B, R′) planes along the shard axis +
                one more total-order top-R′ (inside ``exec.topk``)
    refine    : the codec's second stage on the merged frontier — each
                shard exact-scores the frontier docs it owns, a psum
                assembles them (identity for non-refining codecs)

The codec is resolved through :mod:`repro.core.codecs` (DESIGN.md §7):
this module never inspects codec names — the codec's ``partition`` hook
splits its doc planes and the exec layer routes scoring/refine through
the per-shard :class:`~repro.core.exec.Source`.

The partition happens AFTER global list construction (including
capacity truncation), so the union of the per-shard lists is exactly
the single-device lists — no doc is scored on the sharded path that the
single-device path would have truncated away, and vice versa.  Because
each doc lives in exactly one shard, per-shard dedup is global dedup,
and because top-R selection uses the total order of
:func:`~repro.core.exec.topk_by_score` (score desc, id asc) — and any
refine stage re-ranks the already-merged frontier — the merged result
is **bit-identical** to single-device ``search()`` for every registered
codec, with and without a namespace filter (asserted by
``tests/test_exec.py``).

Per-shard planes keep the *global* list capacity, so the per-shard
candidate budget equals the single-device budget; the win is HBM (each
device holds 1/S of the codec planes) and throughput (S devices
gather+score concurrently), not per-shard budget.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cluster_selector as cs_mod
from repro.core import codecs
from repro.core import exec as qexec
from repro.core import hybrid_index as hi
from repro.core import term_selector as ts_mod
from repro.core.inverted_lists import PAD_DOC, PaddedLists
from repro.distributed import compat

Array = jax.Array

SHARD_AXIS = "shards"


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["cluster_sel", "term_sel", "cluster_entries",
                 "cluster_lengths", "term_entries", "term_lengths",
                 "codec_params", "doc_planes", "doc_assign", "doc_ns",
                 "sparse_weights"],
    meta_fields=["codec", "n_docs"])
@dataclasses.dataclass(frozen=True)
class ShardedHybridIndex:
    """HI² with every document-indexed plane carrying a leading shard
    axis (S, ...).  Selector/codec-param state is replicated."""
    cluster_sel: cs_mod.ClusterSelector     # replicated
    term_sel: ts_mod.TermSelector           # replicated
    cluster_entries: Array                  # (S, L, Cc) i32, global doc ids
    cluster_lengths: Array                  # (S, L) i32
    term_entries: Array                     # (S, V, Ct) i32
    term_lengths: Array                     # (S, V) i32
    codec_params: Any                       # replicated codec state
    doc_planes: dict                        # codec planes, leaves (S, P, ...)
    doc_assign: Array                       # (S, P) i32, φ(D) per shard
    doc_ns: Optional[Array] = None          # (S, P) i32 namespace ids
    sparse_weights: Optional[Array] = None  # (S, V, Ct) f32 BM25 impacts
    #                                         aligned with term_entries
    codec: str = codecs.DEFAULT
    n_docs: int = 0                         # true corpus size (pre-padding)

    @property
    def n_shards(self) -> int:
        return self.cluster_entries.shape[0]

    @property
    def docs_per_shard(self) -> int:
        return self.doc_assign.shape[1]

    # convenience views matching HybridIndex (None when absent)
    @property
    def doc_codes(self) -> Optional[Array]:
        return self.doc_planes.get("codes")

    @property
    def doc_embeddings(self) -> Optional[Array]:
        return self.doc_planes.get("emb")


# --------------------------------------------------------------------------
# partition (host-side, build-time)
# --------------------------------------------------------------------------

def _split_lists(entries: Array, n_shards: int, per: int, base: int = 0,
                 weights: Optional[Array] = None):
    """Filter a global (L, C) entries plane into per-shard planes.

    Keeps the global capacity C per shard and left-packs each row, so
    the union over shards is exactly the global plane (order within a
    list is preserved — which the sparse path relies on: impact order
    survives the split, so per-shard BM25 sums are the same in-order
    float additions as single-device).  Shard ``s`` owns ids in
    [base + s·per, base + (s+1)·per) — ``base`` is 0 for the doc planes
    and ``n_base`` when splitting a delta segment's global ids over its
    slot ranges (repro.core.segments).

    With ``weights`` (an aligned (L, C) impact plane,
    :func:`repro.core.inverted_lists.build_scored`) the same
    permutation splits it too (0.0 beyond each shard's count) and a
    third plane is returned.
    """
    e = np.asarray(entries)
    n_lists, cap = e.shape
    out = np.full((n_shards, n_lists, cap), PAD_DOC, np.int32)
    lengths = np.zeros((n_shards, n_lists), np.int32)
    w = None if weights is None else np.asarray(weights)
    w_out = (None if w is None else
             np.zeros((n_shards, n_lists, cap), np.float32))
    cols = np.arange(cap)[None, :]
    for s in range(n_shards):
        mine = (e >= base + s * per) & (e < base + (s + 1) * per)
        order = np.argsort(~mine, axis=1, kind="stable")   # left-pack
        packed = np.take_along_axis(e, order, axis=1)
        count = mine.sum(axis=1)
        out[s] = np.where(cols < count[:, None], packed, PAD_DOC)
        lengths[s] = count
        if w is not None:
            packed_w = np.take_along_axis(w, order, axis=1)
            w_out[s] = np.where(cols < count[:, None], packed_w, 0.0)
    if w is None:
        return out, lengths
    return out, lengths, w_out


def _split_docs(plane: Array, n_shards: int, per: int) -> np.ndarray:
    """(n_docs, ...) -> (S, P, ...) with zero-padded tail rows (padded
    rows are unreachable: no list entry ever points at them)."""
    x = np.asarray(plane)
    pad = n_shards * per - x.shape[0]
    x = np.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((n_shards, per) + x.shape[1:])


def partition(index: hi.HybridIndex, n_shards: int) -> ShardedHybridIndex:
    """Split a built single-device index into ``n_shards`` contiguous
    document ranges.  Pure host-side numpy; run once at build time."""
    assert n_shards >= 1
    codec_impl = codecs.get(index.codec)
    n_docs = index.n_docs
    per = -(-n_docs // n_shards)    # ceil
    c_entries, c_lengths = _split_lists(index.cluster_lists.entries,
                                        n_shards, per)
    s_weights = None
    if index.sparse_weights is None:
        t_entries, t_lengths = _split_lists(index.term_lists.entries,
                                            n_shards, per)
    else:
        t_entries, t_lengths, s_weights = _split_lists(
            index.term_lists.entries, n_shards, per,
            weights=index.sparse_weights)
    return ShardedHybridIndex(
        cluster_sel=index.cluster_sel,
        term_sel=index.term_sel,
        cluster_entries=jnp.asarray(c_entries),
        cluster_lengths=jnp.asarray(c_lengths),
        term_entries=jnp.asarray(t_entries),
        term_lengths=jnp.asarray(t_lengths),
        codec_params=codec_impl.replicate(index.codec_params),
        doc_planes=codec_impl.partition(
            index.doc_planes,
            lambda x: jnp.asarray(_split_docs(x, n_shards, per))),
        doc_assign=jnp.asarray(_split_docs(index.doc_assign, n_shards, per)),
        doc_ns=(None if index.doc_ns is None else
                jnp.asarray(_split_docs(index.doc_ns, n_shards, per))),
        sparse_weights=(None if s_weights is None else
                        jnp.asarray(s_weights)),
        codec=index.codec,
        n_docs=n_docs)


# --------------------------------------------------------------------------
# placement
# --------------------------------------------------------------------------

def make_shard_mesh(n_shards: int, axis_name: str = SHARD_AXIS) -> Mesh:
    """1-D serving mesh over the first ``n_shards`` local devices.

    On CPU, emulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    devs = jax.devices()
    if len(devs) < n_shards:
        raise RuntimeError(
            f"need {n_shards} devices for {n_shards} shards, have "
            f"{len(devs)}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards}")
    return compat.make_mesh((n_shards,), (axis_name,),
                            devices=devs[:n_shards])


def device_put(sindex: ShardedHybridIndex, mesh: Mesh,
               axis_name: str = SHARD_AXIS) -> ShardedHybridIndex:
    """Place each shard's planes on its device (1/S of the doc-plane
    bytes per device — the HBM win), selectors/codec params replicated."""
    def put_sharded(x):
        return (None if x is None else jax.device_put(
            x, NamedSharding(mesh, P(axis_name, *(None,) * (x.ndim - 1)))))

    def put_rep(t):
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P())), t)

    return dataclasses.replace(
        sindex,
        cluster_sel=put_rep(sindex.cluster_sel),
        term_sel=put_rep(sindex.term_sel),
        codec_params=put_rep(sindex.codec_params),
        cluster_entries=put_sharded(sindex.cluster_entries),
        cluster_lengths=put_sharded(sindex.cluster_lengths),
        term_entries=put_sharded(sindex.term_entries),
        term_lengths=put_sharded(sindex.term_lengths),
        doc_planes=jax.tree.map(put_sharded, sindex.doc_planes),
        doc_assign=put_sharded(sindex.doc_assign),
        doc_ns=put_sharded(sindex.doc_ns),
        sparse_weights=put_sharded(sindex.sparse_weights))


# --------------------------------------------------------------------------
# search
# --------------------------------------------------------------------------

def _shard_planes(sindex: ShardedHybridIndex) -> dict:
    planes = {"cluster_entries": sindex.cluster_entries,
              "cluster_lengths": sindex.cluster_lengths,
              "term_entries": sindex.term_entries,
              "term_lengths": sindex.term_lengths,
              "codec": sindex.doc_planes}
    if sindex.doc_ns is not None:
        planes["doc_ns"] = sindex.doc_ns
    if sindex.sparse_weights is not None:
        planes["sparse_weights"] = sindex.sparse_weights
    return planes


def make_search_step(mesh: Mesh, axis_name: str, codec: str, per: int,
                     kc: int, k2: int, top_r: int,
                     use_kernel: bool = False,
                     batch_axis: Optional[str] = None,
                     filtered: bool = False,
                     fusion: Optional[qexec.FusionSpec] = None):
    """shard_map'd per-shard search + merge for one static config.

    Returns ``step(planes, rep, qe, qt) -> (doc_ids, scores, n_cands)``
    — or, with ``filtered=True``, ``step(planes, rep, qe, qt,
    ns_filter)`` where ``ns_filter`` is the replicated (B, W) uint32
    per-query namespace bitmap and ``planes`` must carry ``doc_ns``.
    The step is un-jitted, so ``launch/cells.py`` can lower it with
    explicit in_shardings.  ``planes`` carries the shard-leading arrays
    with the codec doc planes nested under ``"codec"``; ``rep`` the
    replicated selector state with the codec params under ``"codec"``.
    ``batch_axis`` optionally data-shards the query batch over a second
    mesh axis (the production (data, model) layout: queries over data,
    index shards over model); None replicates queries, which is the 1-D
    serving-mesh case.

    The body is nothing but the §9 stage chain over one per-shard
    :class:`~repro.core.exec.Source` with a
    :class:`~repro.core.exec.ShardEnv` — the same engine as the
    single-device path, so results are bit-identical by construction.
    """
    codec_impl = codecs.get(codec)

    def body(shard, rep, qe, qt, ns_filter=None):
        # shard_map hands this device's block with a leading length-1
        # shard axis; drop it to get the local planes
        shard = jax.tree.map(lambda x: x[0], shard)
        # an explicit per-shard "offsets" plane overrides the contiguous
        # axis_index * per layout — the survivor-set serving path
        # (DESIGN.md §12) keeps global doc ids stable when shard m is
        # ejected and position i no longer owns range [i·per, (i+1)·per)
        offset = shard.get("offsets")
        if offset is None:
            offset = jax.lax.axis_index(axis_name) * per
        source = qexec.Source(
            cluster_lists=PaddedLists(shard["cluster_entries"],
                                      shard["cluster_lengths"]),
            term_lists=PaddedLists(shard["term_entries"],
                                   shard["term_lengths"]),
            doc_planes=shard["codec"],
            size=per,
            offset=offset,
            doc_ns=shard.get("doc_ns"),
            sparse_weights=shard.get("sparse_weights"))
        res = qexec.execute(
            codec_impl, rep["codec"],
            cs_mod.ClusterSelector(embeddings=rep["cluster_emb"]),
            ts_mod.TermSelector(avg_scores=rep["term_avg"]),
            [source], qe, qt,
            kc=kc, k2=k2, top_r=top_r, use_kernel=use_kernel,
            ns_filter=ns_filter, shard=qexec.ShardEnv(axis_name),
            fusion=fusion)
        return res.doc_ids, res.scores, res.n_candidates

    def specs_like(tree, leading):
        return jax.tree.map(
            lambda x: P(leading, *(None,) * (x.ndim - 1)) if leading
            else P(*(None,) * x.ndim), tree)

    qspec = P(batch_axis, None)

    def run(planes, rep, qe, qt, ns_filter=None):
        in_specs = [specs_like(planes, axis_name), specs_like(rep, None),
                    qspec, qspec]
        args = [planes, rep, qe, qt]
        if filtered:
            in_specs.append(qspec)       # bitmap rides with the queries
            args.append(ns_filter)
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(qspec, qspec, P(batch_axis)),
            check_vma=False)  # outputs are replicated over the shard axis by
        #                   construction (merge ends in identical
        #                   all-gathered data on every shard)
        return mapped(*args)

    return run


@functools.lru_cache(maxsize=32)
def _compiled_search(mesh: Mesh, axis_name: str, codec: str, per: int,
                     kc: int, k2: int, top_r: int, use_kernel: bool,
                     filtered: bool, batch_axis: Optional[str] = None,
                     fusion: Optional[qexec.FusionSpec] = None):
    return jax.jit(make_search_step(mesh, axis_name, codec, per,
                                    kc, k2, top_r, use_kernel,
                                    batch_axis=batch_axis,
                                    filtered=filtered, fusion=fusion))


def take_shards(sindex: ShardedHybridIndex,
                shard_ids) -> ShardedHybridIndex:
    """The survivor view: a smaller sharded index holding only the
    given shards' planes (DESIGN.md §12).

    Global doc ids are preserved — list entries still name the original
    corpus positions — but the surviving shards no longer sit at their
    original mesh positions, so searches over the view must pass
    :func:`search` the matching ``shard_offsets`` (``shard_ids · per``);
    without it, shard position i would be misattributed range
    [i·per, (i+1)·per).
    """
    sel = np.asarray(sorted(int(s) for s in shard_ids))
    if sel.size == 0:
        raise ValueError("take_shards needs at least one surviving shard")
    if sel.min() < 0 or sel.max() >= sindex.n_shards:
        raise ValueError(f"shard ids {sel.tolist()} out of range "
                         f"[0, {sindex.n_shards})")
    take = lambda x: None if x is None else x[jnp.asarray(sel)]  # noqa: E731
    return dataclasses.replace(
        sindex,
        cluster_entries=take(sindex.cluster_entries),
        cluster_lengths=take(sindex.cluster_lengths),
        term_entries=take(sindex.term_entries),
        term_lengths=take(sindex.term_lengths),
        doc_planes=jax.tree.map(take, sindex.doc_planes),
        doc_assign=take(sindex.doc_assign),
        doc_ns=take(sindex.doc_ns),
        sparse_weights=take(sindex.sparse_weights))


def shard_offsets_for(shard_ids, per: int) -> np.ndarray:
    """The explicit offsets plane matching :func:`take_shards`."""
    return np.asarray(sorted(int(s) for s in shard_ids),
                      np.int32) * np.int32(per)


def search(sindex: ShardedHybridIndex, query_embeddings: Array,
           query_tokens: Array, *, kc: int, k2: int, top_r: int,
           mesh: Optional[Mesh] = None, axis_name: str = SHARD_AXIS,
           use_kernel: bool = False,
           filter: Optional[Array] = None,
           data_axis: Optional[str] = None,
           shard_offsets: Optional[Array] = None,
           fusion: Optional[qexec.FusionSpec] = None) -> hi.SearchResult:
    """Sharded Eq. 5 — same contract and bit-identical results as
    :func:`repro.core.hybrid_index.search` (DESIGN.md §6), including
    under a per-query namespace ``filter`` (DESIGN.md §9) and under
    hybrid ``fusion`` (DESIGN.md §13; needs an index partitioned from
    one built with ``sparse=True`` — otherwise the dense-only fallback
    applies, exactly as single-device).

    ``mesh`` defaults to a fresh 1-D mesh over the first ``n_shards``
    devices; pass the mesh from :func:`make_shard_mesh` (after
    :func:`device_put`) to reuse placement across calls.

    ``data_axis`` names a second mesh axis to partition the query batch
    over — the 2-D (data, model) serving layout of DESIGN.md §12: the
    index planes replicate along it, each data slice searches its rows
    independently, and the batch size must divide by its length.
    ``shard_offsets`` ((S,) i32) overrides the contiguous s·per doc-id
    layout for survivor views (:func:`take_shards`).
    """
    if mesh is None:
        mesh = make_shard_mesh(sindex.n_shards, axis_name)
    if mesh.shape[axis_name] != sindex.n_shards:
        # a smaller axis would silently drop shards (each device keeps
        # only block [0] of its slice) — corrupt results, so hard-fail
        raise ValueError(
            f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]} "
            f"but the index has {sindex.n_shards} shards")
    if data_axis is not None:
        if data_axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {data_axis!r} "
                             f"(axes: {tuple(mesh.shape)})")
        d = mesh.shape[data_axis]
        if query_embeddings.shape[0] % d:
            raise ValueError(
                f"batch {query_embeddings.shape[0]} does not divide over "
                f"{d} data-axis slices; pad to a multiple of {d}")
    if filter is not None and sindex.doc_ns is None:
        raise ValueError(
            "search(filter=...) needs an index partitioned from one "
            "built with doc_namespaces=")
    rep = {"cluster_emb": sindex.cluster_sel.embeddings,
           "term_avg": sindex.term_sel.avg_scores,
           "codec": sindex.codec_params}
    fn = _compiled_search(mesh, axis_name, sindex.codec,
                          sindex.docs_per_shard, kc, k2, top_r, use_kernel,
                          filter is not None, data_axis, fusion)
    planes = _shard_planes(sindex)
    if shard_offsets is not None:
        off = jnp.asarray(shard_offsets, jnp.int32)
        if off.shape != (sindex.n_shards,):
            raise ValueError(f"shard_offsets shape {off.shape} != "
                             f"({sindex.n_shards},)")
        planes["offsets"] = off
    args = (planes, rep, query_embeddings, query_tokens)
    if filter is not None:
        args += (jnp.asarray(filter, jnp.uint32),)
    ids, scores, n_cand = fn(*args)
    return hi.SearchResult(doc_ids=ids, scores=scores, n_candidates=n_cand)


def candidate_budget(sindex: ShardedHybridIndex, kc: int, k2: int) -> int:
    """Per-shard candidate slots per query (the latency proxy; equals
    the single-device budget because shards keep the global capacity)."""
    return qexec.candidate_budget(
        kc, k2, [(sindex.cluster_entries.shape[2],
                  sindex.term_entries.shape[2])])
