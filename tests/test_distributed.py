"""Distributed-correctness tests. These need >1 device, so each spawns a
fresh interpreter with xla_force_host_platform_device_count set —
keeping the main pytest process at 1 device (per the brief, smoke tests
must see a single device)."""
import os
import subprocess
import sys

import pytest

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.environ.get("PYTHONPATH", "src"))


def _run(script: str) -> None:
    r = subprocess.run([sys.executable, "-c", script], env=_ENV,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_sharded_kmeans_matches_psum_semantics():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import kmeans
from repro.distributed import compat
mesh = compat.make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.key(0), (1024, 16))

fit = jax.shard_map(
    lambda xl: kmeans.kmeans_fit_sharded(jax.random.key(1), xl, 8, n_iters=5),
    mesh=mesh, in_specs=P("data"), out_specs=P())
c_sharded = fit(x)
assert c_sharded.shape == (8, 16)
# cost must beat random init cost (learning happened across shards)
a = kmeans.assign_blocked(x, c_sharded)
cost = float(kmeans.kmeans_cost(x, c_sharded, a))
c0 = x[:8]
cost0 = float(kmeans.kmeans_cost(x, c0, kmeans.assign_blocked(x, c0)))
assert cost < cost0, (cost, cost0)
""")


def test_hierarchical_allreduce_equals_flat():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed import collectives, compat
mesh = compat.make_mesh((2, 4), ("pod", "data"))
g = {"w": jax.random.normal(jax.random.key(0), (16, 8)),
     "b": jax.random.normal(jax.random.key(1), (5,))}   # 5 not divisible by 4

flat = jax.shard_map(
    lambda t: collectives.flat_allreduce(t, ("data", "pod")),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P())
hier = jax.shard_map(
    lambda t: collectives.hierarchical_allreduce(t),
    mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(),
    check_vma=False)  # RS->AR->AG reconstructs replication; not inferable

gs = {"w": jnp.tile(g["w"], (8, 1)), "b": jnp.tile(g["b"], 8)}
a = flat({"w": gs["w"], "b": gs["b"]})
b = hier({"w": gs["w"], "b": gs["b"]})
np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]), rtol=1e-5)
np.testing.assert_allclose(np.asarray(a["b"]), np.asarray(b["b"]), rtol=1e-5)
""")


def test_sharded_hi2_search_matches_single_device():
    """Index-parallel serving: query-sharded search over the mesh equals
    the single-device result (the paper's serving layout, DESIGN.md §2)."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import hybrid_index as hi
from repro.data import synthetic
from repro.distributed import compat, sharding as shd

corpus = synthetic.generate(seed=0, n_docs=4000, n_queries=128,
                            hidden=32, vocab_size=2048, n_topics=32)
idx = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
               jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
               n_clusters=64, k1_terms=8, codec="opq", pq_m=4, pq_k=64,
               cluster_capacity=128, term_capacity=64, kmeans_iters=5)
qe, qt = jnp.asarray(corpus.query_emb), jnp.asarray(corpus.query_tokens)
ref = hi.search(idx, qe, qt, kc=4, k2=4, top_r=20)

mesh = compat.make_mesh((8,), ("data",))
with shd.use_mesh(mesh, {"batch": "data"}):
    qe_s = jax.device_put(qe, NamedSharding(mesh, P("data")))
    qt_s = jax.device_put(qt, NamedSharding(mesh, P("data")))
    out = hi.search(idx, qe_s, qt_s, kc=4, k2=4, top_r=20)
np.testing.assert_array_equal(np.asarray(ref.doc_ids), np.asarray(out.doc_ids))
""")


def test_dryrun_entrypoint_single_cell():
    """The actual dryrun module runs end-to-end for one cheap cell (with a
    reduced device count via env to keep CI fast)."""
    env = dict(os.environ, PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "sasrec",
         "--shape", "serve_p99", "--out", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ok" in r.stdout
