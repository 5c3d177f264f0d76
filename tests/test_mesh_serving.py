"""2-D (data, model) serving mesh (DESIGN.md §12).

Contracts under test:
  · mesh geometry is invisible in results: every (data, model) layout —
    immutable and mutable, filtered and not — returns doc ids/scores
    bit-identical to the single-device server;
  · the serving runtime over a mesh keeps the §10 compile ledger (one
    program per bucket per mesh, never per replica) and round-robins
    computed rows across every data-axis replica;
  · shard loss degrades instead of failing: after ejecting a model-axis
    shard, results come from the survivors' document ranges flagged
    ``partial=True``, equal to a full-corpus oracle with the lost range
    tombstoned; rejoin from checkpoint restores bit-identical full
    results and every membership change bumps the cache epoch.

Multi-device cases spawn a fresh interpreter with
xla_force_host_platform_device_count (the tests/test_sharded.py
pattern); policy/validation checks run in-process on 1 device.
"""
import os
import subprocess
import sys

import pytest

from repro.launch import runtime as rt_mod

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH=os.environ.get("PYTHONPATH", "src"))

_PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import hybrid_index as hi, segments as seg
from repro.core import sharded_index as shi
from repro.launch import serve
from repro.data import synthetic

assert jax.device_count() == 4
corpus = synthetic.generate(seed=0, n_docs=3000, n_queries=48,
                            hidden=32, vocab_size=1024, n_topics=16)
KW = dict(n_clusters=32, k1_terms=6, codec="sq8",
          cluster_capacity=96, term_capacity=48, kmeans_iters=5)

def assert_equal(a, b):
    # full bit-identity: comparisons WITHIN one mesh geometry
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))
    np.testing.assert_array_equal(np.asarray(a.scores),
                                  np.asarray(b.scores))

def assert_match(a, b):
    # ACROSS geometries (DESIGN.md S12): doc ids are bit-identical, but
    # scores may differ by ~1 ulp — XLA picks a different kernel tiling
    # (hence reduction order) for the smaller per-replica row blocks
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))
    np.testing.assert_allclose(np.asarray(a.scores),
                               np.asarray(b.scores), rtol=0, atol=1e-5)
"""


def _run(script: str) -> None:
    r = subprocess.run([sys.executable, "-c", _PRELUDE + script], env=_ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_mesh_geometries_bit_identical():
    """Every (data, model) geometry — including under per-query
    namespace filters — equals the single-device Server."""
    _run("""
ns = np.arange(3000) % 4
idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
               jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
               doc_namespaces=ns, **KW)
base = serve.Server(idx, serve.ServeConfig(max_batch=16, n_namespaces=4))
ref = base.query(corpus.query_emb[:16], corpus.query_tokens[:16])
want = [i % 4 for i in range(16)]
ref_f = base.query(corpus.query_emb[:16], corpus.query_tokens[:16],
                   namespaces=want)
for d, m in ((2, 1), (4, 1), (2, 2), (1, 4)):
    cfg = serve.ServeConfig(max_batch=16, n_shards=m, data_parallel=d,
                            n_namespaces=4)
    srv = (serve.MeshServer(idx, cfg) if d > 1
           else serve.make_server(idx, cfg))
    out = srv.query(corpus.query_emb[:16], corpus.query_tokens[:16])
    assert_match(ref, out)
    assert out.partial is False
    out_f = srv.query(corpus.query_emb[:16], corpus.query_tokens[:16],
                      namespaces=want)
    assert_match(ref_f, out_f)
    # ragged tail batch (pads to max_batch inside the server)
    assert_match(base.query(corpus.query_emb[16:27],
                            corpus.query_tokens[16:27]),
                 srv.query(corpus.query_emb[16:27],
                           corpus.query_tokens[16:27]))
""")


def test_mutable_mesh_2d_bit_identical():
    """ShardedMutableServer on a (2, 2) mesh: add/delete/compact and
    search equal to the single-device MutableServer throughout."""
    _run("""
def build_mut():
    return seg.MutableHybridIndex.create(
        jax.random.key(0), corpus.doc_emb[:-64], corpus.doc_tokens[:-64],
        corpus.vocab_size, delta_capacity=64, **KW)

ref = serve.make_mutable_server(build_mut(), serve.ServeConfig(
    max_batch=16, mutable=True))
mesh2d = serve.make_mutable_server(build_mut(), serve.ServeConfig(
    max_batch=16, mutable=True, n_shards=2, data_parallel=2))
assert type(mesh2d).__name__ == "ShardedMutableServer"
assert mesh2d.mut.data_axis == "data"
for srv in (ref, mesh2d):
    ids = srv.add(corpus.doc_emb[-64:], corpus.doc_tokens[-64:])
    srv.delete(ids[:16])
assert_match(ref.query(corpus.query_emb[:16], corpus.query_tokens[:16]),
             mesh2d.query(corpus.query_emb[:16], corpus.query_tokens[:16]))
ref.compact(); mesh2d.compact()
assert_match(ref.query(corpus.query_emb[:16], corpus.query_tokens[:16]),
             mesh2d.query(corpus.query_emb[:16], corpus.query_tokens[:16]))
""")


def test_runtime_over_mesh_compiles_and_round_robin():
    """One compile per bucket per MESH (not per replica), zero serving
    compiles, computed rows round-robined across both replicas, and
    runtime rows bit-identical to direct mesh serving."""
    _run("""
from repro.launch import runtime as rt_mod
idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
               jnp.asarray(corpus.doc_tokens), corpus.vocab_size, **KW)
srv = serve.make_server(idx, serve.ServeConfig(
    max_batch=16, n_shards=2, data_parallel=2))
assert type(srv).__name__ == "MeshServer" and srv.n_replicas == 2
rt = rt_mod.ServingRuntime(srv, rt_mod.RuntimeConfig())
assert rt.buckets == (4, 8, 16)      # quantum-2 ladder
rt.warmup(32, corpus.query_tokens.shape[1])
assert all(n == 1 for n in rt.warm_traces.values()), rt.warm_traces
with rt:
    for n in (1, 3, 16, 7, 2):
        rt.query(corpus.query_emb[:n], corpus.query_tokens[:n])
    rt.assert_one_compile_per_bucket()
    disp = rt.stats()["replica_dispatch"]
    assert set(disp) == {0, 1} and all(v > 0 for v in disp.values()), disp
    assert sum(disp.values()) == rt.n_served == 29
    direct = srv.query(corpus.query_emb[:16], corpus.query_tokens[:16])
    assert_equal(direct, rt.query(corpus.query_emb[:16],
                                  corpus.query_tokens[:16]))
""")


def test_shard_loss_degrades_and_rejoins_bit_identically():
    """The failover drill: eject -> partial results from the survivor
    ranges (equal to the tombstoned-oracle), runtime carries the flag
    and the epoch bump blocks stale cache replay, rejoin-from-checkpoint
    restores bit-identical full results."""
    _run("""
import tempfile
from repro.launch import runtime as rt_mod
idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
               jnp.asarray(corpus.doc_tokens), corpus.vocab_size, **KW)
srv = serve.MeshServer(idx, serve.ServeConfig(
    max_batch=16, n_shards=2, data_parallel=2))
qe, qt = corpus.query_emb[:16], corpus.query_tokens[:16]
full = srv.query(qe, qt)
assert srv.epoch == 0 and not srv.partial

# every rt.query below is one full max_batch: a long linger makes it
# one micro-batch however the scheduler thread is timed (sq8 scores
# of a smaller bucket may differ from `full` by an ulp)
rt = rt_mod.ServingRuntime(srv, rt_mod.RuntimeConfig(cache_size=64,
                                                     linger_ms=60_000))
rt.warmup(32, qt.shape[1])
pre = rt.query(qe, qt)
assert not pre.partial

with tempfile.TemporaryDirectory() as td:
    path = srv.checkpoint(td)
    srv.eject_shard(0)
    assert srv.partial and srv.epoch == 1
    assert srv.lost_doc_ranges() == [(0, 1500)]
    degraded = srv.query(qe, qt)
    assert degraded.partial is True
    ids = np.asarray(degraded.doc_ids)
    assert (ids[ids >= 0] >= 1500).all()      # nothing from the lost range

    # oracle: the full corpus with the lost range tombstoned (same build
    # key -> same base index; DESIGN.md S12 degradation contract)
    mut = seg.MutableHybridIndex.create(
        jax.random.key(0), corpus.doc_emb, corpus.doc_tokens,
        corpus.vocab_size, delta_capacity=16, **KW)
    mut.delete_docs(np.arange(0, 1500))
    oracle = serve.make_mutable_server(mut, serve.ServeConfig(
        max_batch=16, mutable=True))
    assert_match(oracle.query(qe, qt), degraded)

    # the runtime serves the degraded mesh: partial flag on every row,
    # and the epoch bump means NO replay of pre-failure cached rows
    hits0 = rt.cache.hits
    via_rt = rt.query(qe, qt)
    assert via_rt.partial is True and rt.cache.hits == hits0
    assert_equal(degraded, via_rt)

    # ejecting the last survivor is refused
    try:
        srv.eject_shard(1)
        raise SystemExit("ejecting the last healthy shard must fail")
    except ValueError:
        pass

    srv.rejoin(path)
assert not srv.partial and srv.epoch == 2
restored = srv.query(qe, qt)
assert restored.partial is False
assert_equal(full, restored)
post = rt.query(qe, qt)
assert not post.partial
assert_equal(full, post)
""")


def test_straggler_feed_ejects_through_the_server():
    """note_shard_latency wires fault.ShardHealth into serving: a shard
    consistently missing the rolling-median deadline is ejected after
    MAX_STRIKES, and the mesh keeps serving (partial=True)."""
    _run("""
idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
               jnp.asarray(corpus.doc_tokens), corpus.vocab_size, **KW)
srv = serve.MeshServer(idx, serve.ServeConfig(
    max_batch=16, n_shards=2, data_parallel=1))
for _ in range(10):                    # healthy baseline for the median
    for shard in (0, 1):
        assert not srv.note_shard_latency(shard, 0.1)
ejected = False
for _ in range(5):                     # shard 1 straggles at 10x median
    srv.note_shard_latency(0, 0.1)
    if srv.note_shard_latency(1, 1.0):
        ejected = True
        break
assert ejected and srv.health.lost == [1] and srv.partial
res = srv.query(corpus.query_emb[:16], corpus.query_tokens[:16])
assert res.partial is True
ids = np.asarray(res.doc_ids)
assert (ids[ids >= 0] < 1500).all()    # only shard 0's range
""")


# --------------------------------------------------------------------------
# in-process validation (1 device)
# --------------------------------------------------------------------------

def test_serving_mesh_validation():
    from repro.launch import mesh as mesh_mod

    with pytest.raises(ValueError, match=">= 1"):
        mesh_mod.make_serving_mesh(0, 2)
    with pytest.raises(RuntimeError, match="device_count"):
        mesh_mod.make_serving_mesh(4, 4)    # 16 devices on a 1-device host


def test_mesh_server_rejects_indivisible_batch():
    from repro.launch import serve

    with pytest.raises(ValueError, match="divide"):
        serve.MeshServer(None, serve.ServeConfig(max_batch=16,
                                                 data_parallel=3))


def test_runtime_quantum_follows_server_replicas():
    class _Cfg:
        max_batch = 32
        n_namespaces = 0

    class _FakeMeshServer:
        cfg = _Cfg()
        n_replicas = 4

    rt = rt_mod.ServingRuntime(_FakeMeshServer())
    assert rt.n_replicas == 4
    assert rt.buckets == (8, 16, 32)
    # round-robin placement: injective, replica-major blocks
    place = rt._rows_idx(6, 8)
    assert place == [0, 2, 4, 6, 1, 3]
    assert rt._rows_idx(5, 8)[:4] == [0, 2, 4, 6]
