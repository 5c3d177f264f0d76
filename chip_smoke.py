#!/usr/bin/env python3
"""End-to-end check that the served HI² path runs on a TPU.

    python3 chip_smoke.py              # one chip: build, runtime, kernels
    python3 chip_smoke.py --chips 4    # four chips: sharded layouts only

One chip, in one process, at the ``hi2-synth/serve_msmarco`` widths
(``src/repro/configs/hi2_synth.py``: h=768, L=10,000 clusters,
V=30,528, list capacities 1024, OPQ m=96 k=256, K^C=30, K₂ᵀ=32, R=100,
32-token queries) over a corpus generated on the device from ``--seed``
(``repro.data.synthetic.generate_device``):

  data     the corpus and queries;
  build    ``hybrid_index.build`` (k-means, BM25 term lists, OPQ);
  warmup   ``serve.make_server`` + ``runtime.ServingRuntime``: every
           micro-batch bucket compiled;
  serve    single-query requests through the runtime; result shapes,
           doc-id validity and ordering checked; zero compiles after
           warmup;
  recall   recall@100 against the exact brute-force oracle
           (``codecs.flat.search``), held to RECALL_FLOOR;
  kernel   the same queries with ``use_kernel=True`` (fused Pallas
           scoring and dispatch): the compiled step must contain
           ``tpu_custom_call``, candidate counts must match the XLA path
           exactly and doc ids within DESIGN.md §11's 1e-4 score
           tolerance; both paths' warm batches are timed.

``--chips 4`` builds the same index and queries, then serves them
document-sharded over 4 chips and on the (data, model) = (2, 2) mesh,
requiring doc ids bit-identical to one-chip search in the same process
(DESIGN.md §6, §12), scores too for the 4 shards and within 1e-5 on the
mesh, with each shard on its own chip.

Scale cuts, printed on the first line: the corpus (8,841,984 docs do not
build on one chip's HBM; ``--docs``, default 2^20) and the batch (the
XLA serving step at B=256 needs 19.6 GB of a v5e's 15.75 GB; max_batch
64).  Each phase prints one JSON line; any failure raises and exits
nonzero.  The last line is ``{"ok": true, "device": {...}}``.  Without a
TPU it exits 1 before doing anything, naming the platform it found.
JAX's persistent compilation cache is used: ``JAX_COMPILATION_CACHE_DIR``
if set, else ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: the published corpus and the batch the serve_msmarco cell names
FULL_DOCS, FULL_BATCH = 8_841_984, 256
MAX_BATCH = 64
#: recall@100 of the served path against the exact oracle
RECALL_FLOOR = 0.9
#: DESIGN.md §11: fused vs XLA scores agree to float32 reduction error
SCORE_TOL = 1e-4
#: DESIGN.md §12: scores across mesh geometries agree to ~1 ulp
MESH_SCORE_TOL = 1e-5


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


class Counters:
    """XLA backend compiles (count and seconds) and persistent-cache
    hits/misses, from JAX's own monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, monitoring):
        self.compiles = self.hits = self.misses = 0
        self.compile_seconds = 0.0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == self.COMPILE_EVENT:
            self.compiles += 1
            self.compile_seconds += secs


def corpus_and_index(args, jax, shape):
    from repro.core import hybrid_index as hi
    from repro.data import synthetic

    t0 = time.perf_counter()
    # one topic per cluster (~100 docs each at 2^20 docs) and noise of
    # norm 0.5 / 0.3 around it (per dimension: norm / sqrt(h)), so every
    # query has a real neighborhood; at generate()'s per-dimension
    # defaults, 768 dimensions of noise would swamp the topics and the
    # exact top-100 would be noise too
    per_dim = shape.hidden ** -0.5
    corpus = synthetic.generate_device(
        args.seed, n_docs=args.docs, n_queries=args.queries,
        hidden=shape.hidden, vocab_size=shape.vocab,
        n_topics=shape.n_clusters, query_len=shape.query_len,
        sigma_doc=0.5 * per_dim, sigma_idio=0.3 * per_dim,
        sigma_easy=0.3 * per_dim, sigma_hard=0.5 * per_dim)
    jax.block_until_ready(corpus.doc_emb)
    log(phase="data", docs=args.docs, queries=args.queries,
        seconds=time.perf_counter() - t0)

    t0 = time.perf_counter()
    index = hi.build(jax.random.key(args.seed), corpus.doc_emb,
                     corpus.doc_tokens, shape.vocab,
                     n_clusters=shape.n_clusters, k1_terms=3,
                     codec=shape.codec, pq_m=shape.pq_m, pq_k=shape.pq_k,
                     cluster_capacity=shape.cluster_capacity,
                     term_capacity=shape.term_capacity)
    jax.block_until_ready(index)
    log(phase="build", path="hybrid_index.build", codec=shape.codec,
        seconds=time.perf_counter() - t0,
        peak_hbm_bytes=peak_hbm(jax))
    return corpus, index


def peak_hbm(jax) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.local_devices())


def batched_query(server, np, corpus):
    """Every query through ``server.query`` in max_batch slices."""
    qe, qt = np.asarray(corpus.query_emb), np.asarray(corpus.query_tokens)
    parts = [server.query(qe[i:i + MAX_BATCH], qt[i:i + MAX_BATCH])
             for i in range(0, qe.shape[0], MAX_BATCH)]
    return (np.concatenate([np.asarray(p.doc_ids) for p in parts]),
            np.concatenate([np.asarray(p.scores) for p in parts]),
            np.concatenate([np.asarray(p.n_candidates) for p in parts]))


def one_chip(args, jax, np, shape, counters) -> None:
    from repro.core import hybrid_index as hi
    from repro.core.codecs import flat
    from repro.launch import runtime as rt_mod
    from repro.launch import serve

    corpus, index = corpus_and_index(args, jax, shape)
    cfg = serve.ServeConfig(kc=shape.kc, k2=shape.k2, top_r=shape.top_r,
                            max_batch=MAX_BATCH)

    t0, before = time.perf_counter(), counters.compiles
    compile_s = counters.compile_seconds
    runtime = rt_mod.ServingRuntime(
        serve.make_server(index, cfg),
        rt_mod.RuntimeConfig(queue_depth=args.queries))
    try:
        runtime.warmup(shape.hidden, shape.query_len)
        log(phase="warmup", path="xla", buckets=list(runtime.buckets),
            seconds=time.perf_counter() - t0,
            backend_compiles=counters.compiles - before,
            compile_seconds=counters.compile_seconds - compile_s,
            cache_hits=counters.hits, cache_misses=counters.misses)

        qe, qt = np.asarray(corpus.query_emb), np.asarray(corpus.query_tokens)
        t0, before = time.perf_counter(), counters.compiles
        futures = [runtime.submit(qe[i], qt[i]) for i in range(len(qe))]
        rows = [f.result() for f in futures]
        seconds = time.perf_counter() - t0
        stats = runtime.stats()
        post_compiles = counters.compiles - before
    finally:
        runtime.close()
    ids = np.stack([np.asarray(r.doc_ids) for r in rows])
    scores = np.stack([np.asarray(r.scores) for r in rows])
    n_cand = np.stack([np.asarray(r.n_candidates) for r in rows])
    check(ids.shape == (len(qe), shape.top_r) and ids.dtype == np.int32,
          f"doc_ids shape/dtype {ids.shape} {ids.dtype}")
    check(((ids >= 0) & (ids < args.docs)).all(),
          "a result row holds a padding or out-of-range doc id")
    check(all(len(set(r)) == shape.top_r for r in ids.tolist()),
          "a result row repeats a doc id")
    check(np.isfinite(scores).all() and (np.diff(scores, axis=1) <= 0).all(),
          "scores are not finite and descending")
    check(post_compiles == 0 and stats["post_warmup_traces"] == 0,
          f"{post_compiles} backend compiles / "
          f"{stats['post_warmup_traces']} search traces after warmup")
    runtime.assert_one_compile_per_bucket()
    log(phase="serve", path="xla", requests=len(qe),
        batches=stats["n_batches"], bucket_counts=stats["bucket_counts"],
        seconds=seconds, compiles_after_warmup=post_compiles,
        mean_candidates=float(n_cand.mean()),
        peak_hbm_bytes=peak_hbm(jax))

    t0 = time.perf_counter()
    _, exact = flat.search(corpus.query_emb, corpus.doc_emb, shape.top_r)
    exact = np.asarray(exact)
    recall = float(np.mean([len(set(a) & set(b)) / shape.top_r
                            for a, b in zip(ids.tolist(), exact.tolist())]))
    log(phase="recall", oracle="codecs.flat.search", queries=len(qe),
        recall_at_100=recall, floor=RECALL_FLOOR,
        seconds=time.perf_counter() - t0)
    check(recall >= RECALL_FLOOR,
          f"recall@100 {recall:.4f} below the floor {RECALL_FLOOR}")

    t0, before = time.perf_counter(), counters.compiles
    compile_s = counters.compile_seconds
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    text = hi.search.lower(
        index, jax.numpy.zeros((MAX_BATCH, shape.hidden), "float32"),
        jax.numpy.zeros((MAX_BATCH, shape.query_len), "int32"),
        kc=cfg.kc, k2=cfg.k2, top_r=cfg.top_r,
        use_kernel=True).compile().as_text()
    check("tpu_custom_call" in text,
          "the use_kernel=True step compiled without a Pallas kernel")
    server_k = serve.make_server(index, cfg_k)
    server_k.warmup(shape.hidden, shape.query_len)
    seconds = time.perf_counter() - t0
    # both paths, warm, over the same max_batch slices (host clock; the
    # results are read back to the host, so the device work is done)
    t0 = time.perf_counter()
    batched_query(serve.make_server(index, cfg), np, corpus)
    xla_seconds = time.perf_counter() - t0
    t0 = time.perf_counter()
    k_ids, k_scores, k_cand = batched_query(server_k, np, corpus)
    fused_seconds = time.perf_counter() - t0
    check((k_cand == n_cand).all(),
          "fused and XLA paths evaluated different candidate sets")
    check(np.allclose(k_scores, scores, rtol=SCORE_TOL, atol=SCORE_TOL),
          "fused and XLA scores differ beyond the §11 tolerance")
    swapped = 0
    for q in range(len(qe)):
        diff = set(ids[q].tolist()) ^ set(k_ids[q].tolist())
        if diff:     # only docs tied with the R-th score may trade places
            edge = min(scores[q, -1], k_scores[q, -1])
            both = dict(zip(ids[q].tolist(), scores[q].tolist()))
            both.update(zip(k_ids[q].tolist(), k_scores[q].tolist()))
            check(all(both[d] <= edge + SCORE_TOL for d in diff),
                  f"query {q}: fused and XLA doc ids differ beyond ties")
            swapped += 1
    log(phase="kernel", path="pallas-fused", tpu_custom_call=True,
        queries=len(qe), rows_identical=int(
            (k_ids == ids).all(axis=1).sum()),
        rows_with_tied_swaps=swapped,
        max_abs_score_diff=float(np.abs(k_scores - scores).max()),
        setup_seconds=seconds, backend_compiles=counters.compiles - before,
        compile_seconds=counters.compile_seconds - compile_s,
        batches=-(-len(qe) // MAX_BATCH), xla_seconds=xla_seconds,
        fused_seconds=fused_seconds, peak_hbm_bytes=peak_hbm(jax))


def four_chips(args, jax, np, shape) -> None:
    from repro.launch import serve

    corpus, index = corpus_and_index(args, jax, shape)
    cfg = serve.ServeConfig(kc=shape.kc, k2=shape.k2, top_r=shape.top_r,
                            max_batch=MAX_BATCH)
    t0 = time.perf_counter()
    ref_ids, ref_scores, _ = batched_query(serve.make_server(index, cfg),
                                           np, corpus)
    log(phase="one_chip", seconds=time.perf_counter() - t0)
    # DESIGN.md §6/§12: doc ids are bit-identical on every layout; scores
    # too at the same per-device batch, and within MESH_SCORE_TOL when
    # the data axis halves each replica's rows (another matmul tiling)
    for name, layout, score_tol in (
            ("shards4", dict(n_shards=4), 0.0),
            ("mesh2x2", dict(n_shards=2, data_parallel=2), MESH_SCORE_TOL)):
        t0 = time.perf_counter()
        server = serve.make_server(index, dataclasses.replace(cfg, **layout))
        codes = server.index.doc_planes["codes"]
        devices = {s.device for s in codes.addressable_shards}
        check(len(devices) == 4 and len(codes.sharding.device_set) == 4,
              f"{name}: doc planes on {len(devices)} distinct devices")
        ids, scores, _ = batched_query(server, np, corpus)
        check(np.array_equal(ids, ref_ids),
              f"{name}: doc ids differ from one-chip search")
        max_diff = float(np.abs(scores - ref_scores).max())
        check(max_diff <= score_tol,
              f"{name}: scores differ from one-chip search by {max_diff}")
        log(phase=name, layout=layout, server=type(server).__name__,
            devices=sorted(d.id for d in devices), doc_ids_identical=True,
            max_abs_score_diff=max_diff, seconds=time.perf_counter() - t0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--docs", type=int, default=1 << 20)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: run from a checkout of the repository "
              f"(no src/repro next to {Path(__file__).name})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch import serve
    cache_dir = serve.enable_compile_cache()
    import jax
    import numpy as np
    from jax import monitoring

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    counters = Counters(monitoring)

    from repro.configs import hi2_synth
    shape = dataclasses.replace(hi2_synth.HI2ServeShape("serve_msmarco"),
                                n_docs=args.docs, query_batch=MAX_BATCH)
    log(phase="config", arch="hi2-synth/serve_msmarco", chips=args.chips,
        cuts={"n_docs": [FULL_DOCS, args.docs],
              "max_batch": [FULL_BATCH, MAX_BATCH]},
        compile_cache=cache_dir, device_kind=devices[0].device_kind)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args, jax, np, shape, counters)
    else:
        four_chips(args, jax, np, shape)
    log(phase="done", seconds=time.perf_counter() - t0,
        backend_compiles=counters.compiles,
        compile_seconds=counters.compile_seconds, cache_hits=counters.hits,
        cache_misses=counters.misses, peak_hbm_bytes=peak_hbm(jax))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
