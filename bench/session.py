"""One cell's set-up: the corpus and query pool from the seed, the index
built by the program, and the server over it."""
from __future__ import annotations

import time

import numpy as np

from bench import corpus, derive
from bench.reference import Trained


#: k-means iterations, hybrid_index.build's own
KMEANS_ITERS = 15
#: the precision the codec is trained and encoded at: float32 in full,
#: as the configurations state (the chip's default is one bfloat16
#: pass), so that the reference can derive the same codes
CODEC_PRECISION = "highest"


class Counters:
    """XLA backend compiles (count and seconds) and persistent-cache
    hits and misses, from JAX's own monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

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


class Session:
    """The data, index and server of ``workload`` for ``seed``."""

    def __init__(self, reg, workload: str, seed: int):
        import jax

        from repro.core import cluster_selector as cs
        from repro.core import hybrid_index as hi
        from repro.launch import serve

        self.cell = reg.workload(workload)
        self.cfg = cfg = reg.config(self.cell["config"])
        self.traffic = reg.traffic(self.cell["traffic"])
        self.devices = jax.devices()[:self.cell["chips"]]
        s32 = corpus.seed32(seed)
        self.rng_load, self.rng_check = (
            np.random.default_rng(s)
            for s in np.random.SeedSequence(s32).spawn(2))

        t0 = time.perf_counter()
        self.data = corpus.generate(seed, cfg, int(self.traffic["pool"]))
        self.data_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        # k-means at the chip's default precision, as build() runs it
        # (with build's own split of the key); the codec at full
        key = jax.random.key(s32)
        centres, assign = cs.init_kmeans(
            jax.random.split(key, 3)[0], self.data.doc_emb,
            cfg["n_clusters"], n_iters=KMEANS_ITERS)
        with jax.default_matmul_precision(CODEC_PRECISION):
            self.index = hi.build(
                key, self.data.doc_emb, self.data.doc_tokens, cfg["vocab"],
                n_clusters=cfg["n_clusters"], k1_terms=cfg["k1_terms"],
                codec=cfg["codec"], pq_m=cfg["pq_m"], pq_k=cfg["pq_k"],
                cluster_capacity=cfg["cluster_capacity"],
                term_capacity=cfg["term_capacity"], cluster_sel=centres,
                doc_assign=assign)
        jax.block_until_ready(self.index)
        self.build_s = time.perf_counter() - t0
        self.server = serve.make_server(self.index, serve.ServeConfig(
            kc=cfg["kc"], k2=cfg["k2"], top_r=cfg["top_r"],
            max_batch=cfg["max_batch"], use_kernel=cfg["use_kernel"]))
        self.qe = np.asarray(self.data.query_emb)
        self.qt = np.asarray(self.data.query_tokens)

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def trained(self) -> Trained:
        """The served index's trained parameters, read back to the host:
        all that the plain reference takes from the program."""
        index = self.index
        parts = index.codec.split(":")
        if parts[0] == "refine":
            base = parts[1] if len(parts) > 1 else "pq"
            mult = int(parts[2]) if len(parts) > 2 else 4
        else:
            base, mult = parts[0], 0
        p = index.codec_params
        if base == "opq":
            params = {"rotation": np.asarray(p.rotation),
                      "codewords": np.asarray(p.codebook.codewords)}
        elif base == "sq8":
            params = {}
        else:
            raise ValueError(f"no reference for codec {index.codec!r}")
        return Trained(centroids=np.asarray(index.cluster_sel.embeddings),
                       codec=base, params=params, refine_mult=mult)

    def planes(self, trained: Trained) -> derive.Planes:
        """The reference's own planes over this session's corpus (run
        once the program's state is freed)."""
        return derive.build(self.cfg, trained, self.data.doc_emb,
                            self.data.doc_tokens, self.rows())

    def free(self) -> None:
        """Drop the program's index and server (the corpus stays)."""
        self.index = self.server = None

    def rows(self):
        """Rows of the device corpus by id, gathered in blocks of a few
        fixed sizes (one small program each)."""
        import jax
        import jax.numpy as jnp

        docs = self.data.doc_emb
        take = jax.jit(lambda x, i: x[i])

        def rows(ids):
            ids = np.asarray(ids, np.int64)
            size = 512
            while size < len(ids):
                size *= 2
            pad = np.zeros(size, np.int32)
            pad[:len(ids)] = ids
            return np.asarray(take(docs, jnp.asarray(pad)))[:len(ids)]

        return rows
