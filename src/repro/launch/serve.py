"""Serving driver: a persisted HI² index behind a fixed-shape batched
search step (the production query path, DESIGN.md §2).

    PYTHONPATH=src python -m repro.launch.serve                 # 1 device
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.serve --shards 4      # sharded
    PYTHONPATH=src python -m repro.launch.serve --mutable       # streaming
    PYTHONPATH=src python -m repro.launch.serve --runtime \\
        --linger-ms 2 --cache 1024                  # micro-batched (§10)

Serving layouts:

  · :class:`Server` — the whole index on one device; request batches
    padded to ``max_batch`` so one compiled program serves every
    request size (no recompiles on the hot path).
  · :class:`ShardedServer` — the document-sharded layout of
    DESIGN.md §6: doc planes partitioned over a 1-D device mesh
    (:mod:`repro.core.sharded_index`), per-shard search under
    shard_map, top-R merged by one all-gather.  Bit-identical results,
    1/S of the doc-plane HBM per device.
  · :class:`MeshServer` — the 2-D (data, model) serving mesh of
    DESIGN.md §12 (``--data-parallel D``): doc planes sharded along the
    model axis AND replicated along a data axis over which the query
    batch is partitioned — D× the query throughput of the sharded
    layout, bit-identical results.  Survives model-axis shard loss by
    serving from the survivors' document ranges (``partial=True``)
    until :meth:`MeshServer.rejoin` restores from checkpoint.
  · :class:`MutableServer` / :class:`ShardedMutableServer` — the
    streaming layout of DESIGN.md §8 (``--mutable``): base + delta
    segment + tombstones (:mod:`repro.core.segments`), live
    ``add``/``delete``/``compact`` with no recompiles between
    compactions; the sharded variant routes adds to the owning shard.

Every layout accepts per-query namespace filters (DESIGN.md §9):
build the index with ``--namespaces N`` and pass
``query(..., namespaces=...)`` — one namespace id (or an iterable of
ids) per query — and no document outside those namespaces can appear
in that query's results, on any layout, bit-identically.

Every layout also serves hybrid dense∥sparse fusion (DESIGN.md §13):
``--fusion-weight W`` builds the index with the BM25 impact plane
(``sparse=True``) and fuses the dense ranking with a sparse BM25
ranking by reciprocal-rank fusion; ``W=1.0`` is bit-identical to
dense-only, ``W=0.0`` is pure lexical.  :meth:`Server.set_fusion`
re-weights live (the serving runtime keys its cache on the fusion
spec, so stale fused results can never be replayed).

``--runtime`` puts the asynchronous serving runtime of
:mod:`repro.launch.runtime` (DESIGN.md §10) in front of the chosen
layout: clients submit single queries, a scheduler thread coalesces
them into power-of-two shape buckets (one pre-compiled program each),
an LRU cache short-circuits repeats (``--cache N`` entries, invalidated
by mutations through the index epoch), and a bounded queue
fails fast when overloaded instead of stretching tail latency.

Latency is governed by the static per-query candidate budget
(:func:`repro.core.hybrid_index.candidate_budget` — the proxy all of
``benchmarks/`` reports); ``launch/cells.py::_hi2_serve_cell`` and
``_hi2_sharded_serve_cell`` lower these same steps at MS MARCO scale
for the dry-run.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.checkpoint import checkpoint as ckpt
from repro.core import codecs
from repro.core import exec as qexec
from repro.core import hybrid_index as hi
from repro.core.exec import filters as ns_filters
from repro.core import segments as seg
from repro.core import sharded_index as shi
from repro.distributed import fault
from repro.launch import mesh as mesh_mod


#: the hand-picked width defaults serving falls back to when neither an
#: explicit ServeConfig override nor a tuned index record is present
DEFAULT_KC, DEFAULT_K2 = 6, 8


@dataclasses.dataclass
class ServeConfig:
    # dispatch widths (DESIGN.md §14): None = resolve at server
    # construction — the index's TunedWidths record when present, else
    # DEFAULT_KC/DEFAULT_K2; an explicit value always wins
    kc: Optional[int] = None
    k2: Optional[int] = None
    top_r: int = 100
    max_batch: int = 64
    use_kernel: bool = False     # fused Pallas scoring (--use-kernel, §11)
    n_shards: int = 1            # >1 → document-sharded layout
    mutable: bool = False        # serve a MutableHybridIndex (§8)
    delta_capacity: int = 1024   # delta slots between compactions
    n_namespaces: int = 0        # >0 → filtered search over N namespaces
    data_parallel: int = 1       # >1 → 2-D (data, model) serving mesh (§12)
    # hybrid dense∥sparse fusion (§13): None = dense-only; else the RRF
    # dense weight in [0, 1] (sparse gets 1-w).  Needs an index built
    # with sparse=True, otherwise the dense-only fallback applies.
    fusion_weight: Optional[float] = None
    # per-query adaptive widths (§14): route each query to a rung of
    # the tuned ladder by its dispatch-margin difficulty signal.  Only
    # takes effect when the index carries a multi-rung TunedWidths
    # record and no explicit kc/k2 override is set.
    adaptive: bool = False
    # auto-compaction watermarks (§8): compact when delta fill or
    # tombstone ratio crosses the threshold; 0 disables (the default —
    # serving never compacts behind the operator's back unless asked)
    compact_fill_watermark: float = 0.0
    compact_tombstone_watermark: float = 0.0


def resolve_widths(cfg: ServeConfig, index) -> tuple:
    """Resolve the serving dispatch widths (DESIGN.md §14).

    Resolution order, per field: an explicit ``ServeConfig`` value
    wins, else the index's :class:`repro.core.exec.TunedWidths` record,
    else :data:`DEFAULT_KC`/:data:`DEFAULT_K2`.  Returns
    ``(kc, k2, source)`` where ``source`` is ``"explicit"`` (any field
    overridden), ``"tuned"`` or ``"default"`` — adaptive serving only
    engages when the source is ``"tuned"`` (an operator pinning widths
    pins them for every query).
    """
    tuned = getattr(index, "tuned", None)
    fb_kc = tuned.kc if tuned is not None else DEFAULT_KC
    fb_k2 = tuned.k2 if tuned is not None else DEFAULT_K2
    if cfg.kc is not None or cfg.k2 is not None:
        return (int(cfg.kc if cfg.kc is not None else fb_kc),
                int(cfg.k2 if cfg.k2 is not None else fb_k2), "explicit")
    if tuned is not None:
        return int(tuned.kc), int(tuned.k2), "tuned"
    return DEFAULT_KC, DEFAULT_K2, "default"


class Server:
    """Pads request batches to max_batch so one compiled program serves
    every request size (no recompiles on the hot path)."""

    def __init__(self, index: hi.HybridIndex, cfg: ServeConfig = ServeConfig()):
        self.index = index
        self.cfg = cfg
        self._resolve_widths(index)
        # hi.search is already jitted (static kc/k2/top_r/use_kernel/
        # fusion) — dispatch through a bound method instead of wrapping
        # in a second jax.jit, which would pay nested-jit dispatch on
        # every request; reading cfg at call time lets set_fusion()
        # re-weight live (one compile per distinct FusionSpec)
        self._search = self._base_search
        self.n_served = 0

    def _resolve_widths(self, index) -> None:
        """Resolve (kc, k2) once at construction (DESIGN.md §14) —
        stable across mutations/compactions, like the codec spec."""
        self.tuned = getattr(index, "tuned", None)
        self.kc, self.k2, self.width_source = resolve_widths(self.cfg,
                                                             index)

    def _base_search(self, idx, qe, qt, filter=None,
                     widths=None) -> hi.SearchResult:
        kc, k2 = widths if widths is not None else (self.kc, self.k2)
        return hi.search(idx, qe, qt, kc=kc, k2=k2,
                         top_r=self.cfg.top_r,
                         use_kernel=self.cfg.use_kernel,
                         filter=filter, fusion=self.fusion)

    @classmethod
    def from_checkpoint(cls, path: str, like: hi.HybridIndex,
                        cfg: ServeConfig = ServeConfig()) -> "Server":
        return cls(ckpt.restore_index(path, like), cfg)

    @property
    def epoch(self) -> int:
        """Index mutation counter (DESIGN.md §10) — constant 0 here:
        an immutable index never invalidates cached results.  Mutable
        servers override with the live counter."""
        return 0

    @property
    def n_replicas(self) -> int:
        """Data-axis replica slices (DESIGN.md §12) — the runtime's
        batch quantum: every micro-batch bucket must divide into equal
        per-replica row blocks.  1 on every non-mesh layout."""
        return max(1, int(self.cfg.data_parallel))

    @property
    def _adaptive_ladder(self) -> bool:
        t = self.tuned
        return (self.cfg.adaptive and t is not None and len(t.rungs) > 1
                and self.width_source != "explicit")

    @property
    def rungs(self) -> tuple:
        """The static width ladder adaptive serving compiles, narrow →
        wide (DESIGN.md §14).  A single rung — the resolved (kc, k2) —
        unless adaptivity is on, the index carries a multi-rung tuned
        record, and no explicit override pinned the widths."""
        if self._adaptive_ladder:
            return tuple((int(kc), int(k2)) for kc, k2 in self.tuned.rungs)
        return ((self.kc, self.k2),)

    @property
    def margin_cuts(self) -> tuple:
        """Descending margin thresholds between the rungs (one fewer
        than :attr:`rungs`); empty in the single-rung case."""
        if self._adaptive_ladder:
            return tuple(float(c) for c in self.tuned.margin_cuts)
        return ()

    @property
    def fusion(self) -> Optional[qexec.FusionSpec]:
        """The active hybrid-fusion spec (DESIGN.md §13), derived from
        ``cfg.fusion_weight`` at call time so :meth:`set_fusion` takes
        effect without rebuilding the server.  None = dense-only."""
        w = self.cfg.fusion_weight
        return None if w is None else qexec.FusionSpec(weight=float(w))

    def set_fusion(self, weight: Optional[float]) -> None:
        """Re-weight (or disable, with None) hybrid fusion live.  Takes
        effect on the next query; each distinct weight compiles once
        (the spec is a static argument of the search program)."""
        if weight is not None:
            qexec.FusionSpec(weight=float(weight))  # validate eagerly
        self.cfg.fusion_weight = weight

    def warmup(self, hidden: int, query_len: int) -> None:
        qe = jnp.zeros((self.cfg.max_batch, hidden), jnp.float32)
        qt = jnp.full((self.cfg.max_batch, query_len), -1, jnp.int32)
        jax.block_until_ready(self._search(self.index, qe, qt))

    def _pad(self, query_emb: np.ndarray, query_tokens: np.ndarray):
        n = query_emb.shape[0]
        pad = self.cfg.max_batch - n
        assert pad >= 0, f"batch {n} exceeds max_batch {self.cfg.max_batch}"
        qe = jnp.asarray(np.pad(query_emb, ((0, pad), (0, 0))))
        qt = jnp.asarray(np.pad(query_tokens, ((0, pad), (0, 0)),
                                constant_values=-1))
        return n, qe, qt

    def _filter(self, namespaces, n: int):
        """Per-query ``namespaces`` (one id or iterable of ids per
        query, length n) → the padded (max_batch, W) bitmap; padded
        query rows match nothing (like the PAD query tokens)."""
        if namespaces is None:
            return None
        if not self.cfg.n_namespaces:
            raise ValueError(
                "this server was built without namespaces; construct "
                "with ServeConfig(n_namespaces=N) / --namespaces N")
        if len(namespaces) != n:
            raise ValueError(f"{len(namespaces)} filter rows for {n} "
                             "queries")
        bitmap = ns_filters.make_filter(namespaces, self.cfg.n_namespaces)
        return ns_filters.pad_filter(bitmap, self.cfg.max_batch)

    def query(self, query_emb: np.ndarray, query_tokens: np.ndarray,
              namespaces=None) -> hi.SearchResult:
        """One padded batch through the search program.  Host spans
        (DESIGN.md §9): ``hi2.query`` (``id`` = queries served before
        this call) over ``hi2.query.pad``, ``hi2.query.search`` (the
        program's enqueue) and ``hi2.query.split`` (the call's rows; its
        read of the ``partial`` flag waits for the program to finish)."""
        with spans.span("hi2.query", id=self.n_served):
            with spans.span("hi2.query.pad"):
                n, qe, qt = self._pad(query_emb, query_tokens)
                ns = self._filter(namespaces, n)
            with spans.span("hi2.query.search"):
                res = self._search(self.index, qe, qt, filter=ns)
            self.n_served += n
            with spans.span("hi2.query.split"):
                return hi.SearchResult(
                    doc_ids=res.doc_ids[:n],
                    scores=res.scores[:n],
                    n_candidates=res.n_candidates[:n],
                    partial=bool(np.asarray(getattr(res, "partial",
                                                    False))))

    # mutation API — live only on the mutable servers below
    def add(self, doc_emb: np.ndarray, doc_tokens: np.ndarray,
            namespaces=None) -> np.ndarray:
        raise RuntimeError("this server is immutable; construct with "
                           "ServeConfig(mutable=True) / --mutable to "
                           "enable add/delete/compact")

    def delete(self, doc_ids) -> None:
        self.add(None, None)     # same immutability error

    def compact(self) -> None:
        self.add(None, None)


class ShardedServer(Server):
    """Document-sharded serving (DESIGN.md §6): same request contract
    and bit-identical results as :class:`Server`, index split over
    ``cfg.n_shards`` devices."""

    def __init__(self, index: hi.HybridIndex,
                 cfg: ServeConfig = ServeConfig(),
                 mesh=None):
        self.cfg = cfg
        # widths resolve from the input index: the sharded form drops
        # the tuned record (it is per-index metadata, not per-shard)
        self._resolve_widths(index)
        self.mesh = mesh or shi.make_shard_mesh(cfg.n_shards)
        self.index = shi.device_put(shi.partition(index, cfg.n_shards),
                                    self.mesh)
        self._search = self._sharded_search
        self.n_served = 0

    def _sharded_search(self, idx, qe, qt, filter=None,
                        widths=None) -> hi.SearchResult:
        kc, k2 = widths if widths is not None else (self.kc, self.k2)
        return shi.search(idx, qe, qt, kc=kc, k2=k2,
                          top_r=self.cfg.top_r, mesh=self.mesh,
                          use_kernel=self.cfg.use_kernel, filter=filter,
                          fusion=self.fusion)


class MeshServer(Server):
    """2-D (data, model) mesh serving with shard-loss degradation
    (DESIGN.md §12).

    The index is partitioned into ``cfg.n_shards`` document shards along
    the model axis and replicated along ``cfg.data_parallel`` data-axis
    slices; each slice searches its block of the query batch
    independently, so throughput scales with the data axis while every
    result stays bit-identical to the single-device search (the §6 merge
    runs per-replica over the model axis only).

    Survivability: :meth:`eject_shard` drops one model-axis shard from
    the serving set — requests keep being served from the survivors'
    document ranges, flagged ``partial=True`` — and :meth:`rejoin`
    restores the full mesh from a :meth:`checkpoint`, bit-identical to
    the pre-failure results.  Both bump :attr:`epoch`, so runtime caches
    can never replay full results while degraded or vice versa.
    """

    def __init__(self, index: hi.HybridIndex,
                 cfg: ServeConfig = ServeConfig(), mesh=None):
        data, model = max(1, int(cfg.data_parallel)), int(cfg.n_shards)
        if cfg.max_batch % data:
            raise ValueError(
                f"max_batch {cfg.max_batch} must divide over "
                f"{data} data-axis slices")
        self.cfg = cfg
        self._resolve_widths(index)
        self.data, self.model = data, model
        self.data_axis = "data"
        self.mesh = mesh or mesh_mod.make_serving_mesh(data, model)
        self._full = shi.device_put(shi.partition(index, model), self.mesh)
        self.index = self._full
        # zero-memory restore template (shapes/dtypes, no plane bytes):
        # rejoin-from-checkpoint must not depend on live full-mesh state
        self._template = jax.tree.map(
            lambda x: np.broadcast_to(np.zeros((), x.dtype), x.shape),
            self._full)
        self.health = fault.ShardHealth(model)
        self._survivor = None    # (sub_index, sub_mesh, offsets) | None
        self._mesh_epoch = 0
        self._search = self._mesh_search
        self.n_served = 0

    @property
    def epoch(self) -> int:
        """Bumps on every membership change (eject/rejoin) — degraded
        and full results must never share a cache namespace."""
        return self._mesh_epoch

    @property
    def partial(self) -> bool:
        return self.health.degraded

    def _mesh_search(self, idx, qe, qt, filter=None,
                     widths=None) -> hi.SearchResult:
        kc, k2 = widths if widths is not None else (self.kc, self.k2)
        da = self.data_axis if self.data > 1 else None
        if self._survivor is None:
            return shi.search(self._full, qe, qt, kc=kc,
                              k2=k2, top_r=self.cfg.top_r,
                              mesh=self.mesh,
                              use_kernel=self.cfg.use_kernel,
                              filter=filter, data_axis=da,
                              fusion=self.fusion)
        sub, sub_mesh, offsets = self._survivor
        res = shi.search(sub, qe, qt, kc=kc, k2=k2,
                         top_r=self.cfg.top_r, mesh=sub_mesh,
                         use_kernel=self.cfg.use_kernel, filter=filter,
                         data_axis=da, shard_offsets=offsets,
                         fusion=self.fusion)
        return res._replace(partial=True)

    # --- shard-loss degradation + recovery -------------------------------
    def note_shard_latency(self, shard: int, dt: float) -> bool:
        """Feed one measured per-shard latency into the straggler policy
        (:class:`repro.distributed.fault.ShardHealth`); ejects the shard
        and returns True once it crosses ``MAX_STRIKES`` deadline
        misses."""
        if self.health.observe(shard, dt):
            self.eject_shard(shard)
            return True
        return False

    def eject_shard(self, shard: int) -> None:
        """Drop one model-axis shard from the serving set: subsequent
        queries are served from the survivors' document ranges and
        flagged ``partial=True``.  Idempotent per shard; the last
        healthy shard cannot be ejected."""
        if shard in self.health.lost:
            return
        self.health.eject(shard)
        survivors = self.health.healthy
        sub_mesh = mesh_mod.make_serving_mesh(self.data, len(survivors))
        sub = shi.device_put(shi.take_shards(self._full, survivors),
                             sub_mesh)
        offsets = shi.shard_offsets_for(survivors,
                                        self._full.docs_per_shard)
        self._survivor = (sub, sub_mesh, offsets)
        self._mesh_epoch += 1

    def lost_doc_ranges(self) -> list:
        """[lo, hi) global doc-id ranges currently missing from results
        — the degradation contract surface (DESIGN.md §12)."""
        per, n = self._full.docs_per_shard, self._full.n_docs
        return [(m * per, min((m + 1) * per, n)) for m in self.health.lost]

    def checkpoint(self, directory: str, step: int = 0) -> str:
        """Persist the full sharded index (codec spec recorded in the
        manifest); the path feeds :meth:`rejoin`."""
        return ckpt.save_index(directory, step, self._full)

    def rejoin(self, checkpoint_path: str) -> None:
        """Restore the full mesh from a checkpoint: every lost shard
        returns, results are bit-identical to pre-failure full-mesh
        serving (one more epoch bump keeps caches honest)."""
        restored = ckpt.restore_index(checkpoint_path, self._template)
        self._full = shi.device_put(restored, self.mesh)
        self.index = self._full
        self.health.rejoin()
        self._survivor = None
        self._mesh_epoch += 1


class MutableServer(Server):
    """Serving over a :class:`repro.core.segments.MutableHybridIndex`
    (DESIGN.md §8): the same padded-batch request contract as
    :class:`Server`, plus live ``add``/``delete``/``compact``.  Mutation
    changes plane values, never shapes, so the compiled search program
    is reused across mutations; ``compact()`` swaps in the fresh base
    (one recompile per compaction, never per request)."""

    def __init__(self, mut: seg.MutableHybridIndex,
                 cfg: ServeConfig = ServeConfig()):
        self.mut = mut
        self.cfg = cfg
        self._resolve_widths(mut.base)
        self.index = mut.base    # for the padded-query plumbing only
        self._search = self._mut_search
        self.n_served = 0

    def _mut_search(self, idx, qe, qt, filter=None,
                    widths=None) -> hi.SearchResult:
        kc, k2 = widths if widths is not None else (self.kc, self.k2)
        return self.mut.search(qe, qt, kc=kc, k2=k2,
                               top_r=self.cfg.top_r,
                               use_kernel=self.cfg.use_kernel,
                               filter=filter, fusion=self.fusion)

    @property
    def epoch(self) -> int:
        """The mutable index's mutation counter: bumps on every
        ``add``/``delete`` and across ``compact`` — the cache
        invalidation key of the serving runtime (DESIGN.md §10)."""
        return self.mut.epoch

    def add(self, doc_emb: np.ndarray, doc_tokens: np.ndarray,
            namespaces=None) -> np.ndarray:
        """Index new documents; returns their global doc ids.  On a
        namespaced server ``namespaces`` (scalar or (n,) ids) is
        required."""
        ids = self.mut.add_docs(doc_emb, doc_tokens,
                                namespaces=namespaces)
        self._auto_compact()
        return ids

    def delete(self, doc_ids) -> None:
        """Tombstone documents; they can never appear in results again."""
        self.mut.delete_docs(doc_ids)
        self._auto_compact()

    def _auto_compact(self) -> None:
        """Watermark-driven compaction (DESIGN.md §8): compact when the
        delta fill or tombstone ratio crosses its configured threshold.
        Both watermarks default to 0.0 = disabled — serving never
        compacts behind the operator's back unless asked."""
        fill = self.cfg.compact_fill_watermark
        tomb = self.cfg.compact_tombstone_watermark
        if fill <= 0.0 and tomb <= 0.0:
            return
        host = getattr(self.mut, "mut", self.mut)
        if host.needs_compact(fill_watermark=fill, tombstone_watermark=tomb):
            self.compact()

    def compact(self) -> None:
        """Fold delta + tombstones into a fresh base (bit-identical to a
        from-scratch rebuild over the surviving corpus)."""
        self.mut = self.mut.compact()
        self.index = self.mut.base


class ShardedMutableServer(MutableServer):
    """Mutable + document-sharded: adds are routed to the owning shard
    (``repro.core.segments.ShardedMutableIndex``), results stay
    bit-identical to the single-device :class:`MutableServer`."""

    def __init__(self, mut: seg.MutableHybridIndex,
                 cfg: ServeConfig = ServeConfig(), mesh=None):
        data = max(1, int(cfg.data_parallel))
        if data > 1:
            if cfg.max_batch % data:
                raise ValueError(
                    f"max_batch {cfg.max_batch} must divide over "
                    f"{data} data-axis slices")
            mesh = mesh or mesh_mod.make_serving_mesh(data, cfg.n_shards)
            smut = seg.ShardedMutableIndex(mut, cfg.n_shards, mesh,
                                           data_axis="data")
        else:
            smut = seg.ShardedMutableIndex(mut, cfg.n_shards, mesh)
        self.mut = smut
        self.cfg = cfg
        self._resolve_widths(mut.base)
        self.index = smut.mut.base
        self._search = self._mut_search
        self.n_served = 0

    def compact(self) -> None:
        self.mut = self.mut.compact()
        self.index = self.mut.mut.base


def make_server(index: hi.HybridIndex, cfg: ServeConfig) -> Server:
    if cfg.mutable:
        raise ValueError("make_server serves a built immutable index; "
                         "use make_mutable_server(mut, cfg) for "
                         "ServeConfig(mutable=True)")
    if cfg.data_parallel > 1:
        return MeshServer(index, cfg)
    return ShardedServer(index, cfg) if cfg.n_shards > 1 else Server(index,
                                                                     cfg)


def make_mutable_server(mut: seg.MutableHybridIndex,
                        cfg: ServeConfig) -> MutableServer:
    if cfg.n_shards > 1:
        return ShardedMutableServer(mut, cfg)
    return MutableServer(mut, cfg)


#: the entry points' compile cache when JAX_COMPILATION_CACHE_DIR is not
#: set: a fixed path inside the checkout, so a later run finds it again
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point;
    returns its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
    used as JAX reads it and nothing else is set; otherwise the cache
    goes to :data:`DEFAULT_COMPILE_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def main(argv: Optional[list] = None) -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description="HI² serving demo loop")
    ap.add_argument("--shards", type=int, default=1,
                    help="document shards (devices); on CPU emulate with "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--docs", type=int, default=8000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--codec", default=codecs.DEFAULT,
                    metavar="|".join(codecs.registered()),
                    help="any registered codec spec, e.g. sq8 or refine:pq:4")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--kc", type=int, default=None,
                    help="clusters probed per query; default = the "
                         "index's tuned record if present, else "
                         f"{DEFAULT_KC} (DESIGN.md §14)")
    ap.add_argument("--k2", type=int, default=None,
                    help="term lists probed per query; default = the "
                         "index's tuned record if present, else "
                         f"{DEFAULT_K2}")
    ap.add_argument("--adaptive", action="store_true",
                    help="per-query adaptive widths over the tuned rung "
                         "ladder (needs an index tuned by "
                         "repro.launch.tune; DESIGN.md §14)")
    ap.add_argument("--mutable", action="store_true",
                    help="serve a mutable index and demo live "
                         "add/delete/compact (DESIGN.md §8)")
    ap.add_argument("--delta-capacity", type=int, default=1024,
                    help="delta slots between compactions (--mutable)")
    ap.add_argument("--namespaces", type=int, default=0,
                    help="partition the corpus into N namespaces and demo "
                         "per-query filtered search (DESIGN.md §9)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="score candidates with the fused Pallas kernels "
                         "(DESIGN.md §11; interpret-mode on CPU)")
    ap.add_argument("--fusion-weight", type=float, default=None,
                    metavar="W",
                    help="hybrid dense∥sparse serving (DESIGN.md §13): "
                         "build the BM25 impact plane and fuse dense and "
                         "sparse rankings by RRF with dense weight W in "
                         "[0,1] (1.0 = dense-only, 0.0 = pure lexical)")
    ap.add_argument("--runtime", action="store_true",
                    help="serve through the micro-batching runtime "
                         "(DESIGN.md §10) instead of direct batched calls")
    ap.add_argument("--linger-ms", type=float, default=2.0,
                    help="max wait of the oldest queued request for "
                         "co-riders before its bucket executes (--runtime)")
    ap.add_argument("--cache", type=int, default=0,
                    help="LRU query-result cache entries, 0 = off "
                         "(--runtime)")
    ap.add_argument("--data-parallel", type=int, default=1,
                    help="data-axis replica slices for the 2-D serving "
                         "mesh (DESIGN.md §12); needs shards x replicas "
                         "devices")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="with --runtime: serve plaintext metrics on "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral)")
    args = ap.parse_args(argv)
    codecs.get(args.codec)   # fail fast (with the registered names) on typos

    from repro.data import synthetic
    corpus = synthetic.generate(seed=0, n_docs=args.docs,
                                n_queries=args.queries,
                                hidden=64, vocab_size=4096)
    build_kwargs = dict(n_clusters=128, k1_terms=10, codec=args.codec,
                        pq_m=8, pq_k=256, cluster_capacity=192,
                        term_capacity=96, kmeans_iters=8,
                        sparse=args.fusion_weight is not None)
    cfg = ServeConfig(kc=args.kc, k2=args.k2, adaptive=args.adaptive,
                      max_batch=args.batch, n_shards=args.shards,
                      use_kernel=args.use_kernel,
                      mutable=args.mutable,
                      delta_capacity=args.delta_capacity,
                      n_namespaces=args.namespaces,
                      data_parallel=args.data_parallel,
                      fusion_weight=args.fusion_weight)
    # round-robin tenant assignment for the demo corpus
    doc_ns = (np.arange(args.docs) % args.namespaces
              if args.namespaces else None)
    if args.mutable:
        if args.docs < 512:
            sys.exit("--mutable demo needs --docs >= 512 (the base build "
                     "must keep enough docs for KMeans after the held-out "
                     "stream is split off)")
        # stream the last ~1/8 of the corpus in live, then compact;
        # never more than the delta can hold or half the corpus
        held = max(args.batch, args.docs // 8)
        held = min(held, args.delta_capacity, args.docs // 2)
        mut = seg.MutableHybridIndex.create(
            jax.random.key(0), corpus.doc_emb[:-held],
            corpus.doc_tokens[:-held], corpus.vocab_size,
            delta_capacity=args.delta_capacity,
            doc_namespaces=None if doc_ns is None else doc_ns[:-held],
            **build_kwargs)
        server = make_mutable_server(mut, cfg)
    else:
        index = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
                         jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                         doc_namespaces=doc_ns, **build_kwargs)
        server = make_server(index, cfg)
    metrics = None
    if args.runtime:
        from repro.launch import runtime as rt_mod
        front = rt_mod.ServingRuntime(
            server, rt_mod.RuntimeConfig(
                linger_ms=args.linger_ms, cache_size=args.cache,
                # the demo submits whole batches back-to-back; admission
                # control must not reject its own driver loop
                queue_depth=max(256, 2 * args.batch)))
        front.warmup(64, corpus.query_tokens.shape[1])
        if args.metrics_port is not None:
            metrics = front.serve_metrics(args.metrics_port)
            print(f"metrics: http://127.0.0.1:{metrics.port}/metrics")
    else:
        front = server
        server.warmup(64, corpus.query_tokens.shape[1])
    t0 = time.perf_counter()
    for i in range(0, args.queries, args.batch):
        front.query(corpus.query_emb[i:i + args.batch],
                    corpus.query_tokens[i:i + args.batch])
    dt = time.perf_counter() - t0
    if args.data_parallel > 1:
        layout = f"({args.data_parallel}, {args.shards}) mesh"
    elif args.shards > 1:
        layout = f"{args.shards} shard(s)"
    else:
        layout = "1 device"
    print(f"served {server.n_served} queries in {dt:.3f}s "
          f"({server.n_served / dt:.0f} q/s, {layout})")
    if args.namespaces:
        # each query restricted to one tenant; results must honor it
        b = min(args.batch, args.queries)
        want = [i % args.namespaces for i in range(b)]
        res = front.query(corpus.query_emb[:b], corpus.query_tokens[:b],
                          namespaces=want)
        ids = np.asarray(res.doc_ids)
        ok = all((ids[i][ids[i] >= 0] % args.namespaces == want[i]).all()
                 for i in range(b))
        print(f"filtered: {b} queries x 1/{args.namespaces} namespaces, "
              f"mean candidates "
              f"{float(np.asarray(res.n_candidates).mean()):.0f}, "
              f"tenant isolation {'OK' if ok else 'VIOLATED'}")
        if not ok:
            sys.exit("namespace filter violated tenant isolation")
    if args.mutable:
        ids = front.add(corpus.doc_emb[-held:], corpus.doc_tokens[-held:],
                        namespaces=(None if not args.namespaces else
                                    doc_ns[-held:]))
        front.query(corpus.query_emb[:args.batch],
                    corpus.query_tokens[:args.batch])
        front.delete(ids[: held // 4])
        t0 = time.perf_counter()
        front.compact()
        dt_c = time.perf_counter() - t0
        mut_idx = server.mut
        print(f"mutable: added {held}, deleted {held // 4}, "
              f"compacted to {getattr(mut_idx, 'mut', mut_idx).n_base} "
              f"docs in {dt_c:.2f}s")
    if args.runtime:
        if metrics is not None:
            metrics.close()
        front.close(drain=True)
        s = front.stats()
        cache = s["cache"]
        print(f"runtime: {s['n_batches']} batches over buckets "
              f"{s['buckets']} (counts {s['bucket_counts']}), "
              f"compiles/bucket {s['warm_traces']}, "
              f"{s['post_warmup_traces']} post-warmup compiles"
              + ("" if cache is None else
                 f", cache {cache['hits']} hits / {cache['misses']} "
                 f"misses"))


if __name__ == "__main__":
    main()
