"""Stage scopes and host spans (DESIGN.md §9).

Every operation JAX lowers from the search body carries exactly one
``hi2.<stage>`` scope in the compiled program's ``op_name`` metadata, on
every variant (the sharded one on 4 emulated devices in a fresh
interpreter, the tests/test_exec.py pattern).  The span helper nests
children under their parent, carries attributes, and keeps nothing
while recording is off; ``Server.query`` and ``hybrid_index.build``
emit their spans, and recording changes no result.
"""
import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import hybrid_index as hi
from repro.core import segments as seg
from repro.core.exec import FusionSpec, filters as ns_filters, stages
from repro.data import synthetic
from repro.launch import serve

#: the entry instructions that do a stage's work
KINDS = ("fusion", "custom-call", "sort", "gather", "dot")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+"
                    r"([\w\-]+)\(")


def stage_violations(hlo_text, kinds=KINDS, allowed=stages.STAGES):
    """Entry instructions of ``kinds`` whose op_name JAX wrote (a
    ``jit(`` path) carries other than exactly one allowed hi2 stage;
    and how many were checked.  Instructions XLA adds without an
    op_name (copies, layout changes) are not the program's to scope."""
    entry = hlo_text[hlo_text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    bad, n = [], 0
    for line in entry.splitlines()[1:]:
        m = _INSTR.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m is None or m.group(2) not in kinds or op is None \
                or "jit(" not in op.group(1):
            continue
        n += 1
        found = set(re.findall(r"(?:^|/)hi2\.(\w+)", op.group(1)))
        if len(found) != 1 or not found <= set(allowed):
            bad.append((m.group(1), op.group(1)))
    return bad, n


@pytest.fixture(autouse=True)
def _no_recording_left():
    yield
    spans.stop()


@pytest.fixture(scope="module")
def corpus():
    return synthetic.generate(seed=0, n_docs=900, n_queries=8, hidden=32,
                              vocab_size=512, n_topics=8)


KW = dict(n_clusters=16, k1_terms=4, pq_m=4, pq_k=64,
          cluster_capacity=96, term_capacity=48, kmeans_iters=3)


def _index(c, **extra):
    return hi.build(jax.random.key(0), jnp.asarray(c.doc_emb),
                    jnp.asarray(c.doc_tokens), c.vocab_size,
                    codec="refine:sq8:4", **KW, **extra)


def _compiled(fn, *args, **kw):
    return fn.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("variant", ["base", "mutable", "filtered", "fused"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_every_search_op_lies_in_one_stage(corpus, variant, use_kernel):
    c = corpus
    qe, qt = jnp.asarray(c.query_emb), jnp.asarray(c.query_tokens)
    w = dict(kc=4, k2=4, top_r=10, use_kernel=use_kernel)
    if variant == "mutable":
        mut = seg.MutableHybridIndex.create(
            jax.random.key(0), c.doc_emb, c.doc_tokens, c.vocab_size,
            delta_capacity=32, codec="refine:sq8:4", **KW)
        mut._materialize()
        delta, tomb = mut._cache
        text = _compiled(seg.search, mut.base, delta, tomb, qe, qt, **w)
        want = {"dispatch", "gather", "dedup", "filter", "score", "topk",
                "refine"}
    elif variant == "filtered":
        idx = _index(c, doc_namespaces=np.arange(900) % 4)
        ns = ns_filters.make_filter([[b % 4] for b in range(8)], 4)
        text = _compiled(hi.search, idx, qe, qt, filter=ns, **w)
        want = {"filter"}
    elif variant == "fused":
        idx = _index(c, sparse=True)
        text = _compiled(hi.search, idx, qe, qt,
                         fusion=FusionSpec(weight=0.5), **w)
        want = {"sparse", "fuse"}
    else:
        text = _compiled(hi.search, _index(c), qe, qt, **w)
        want = {"dispatch", "gather", "dedup", "score", "topk", "refine"}
    bad, n = stage_violations(text)
    assert n > 0 and not bad, bad[:5]
    found = set(re.findall(r'op_name="[^"]*?/hi2\.(\w+)', text))
    assert want <= found, (want - found)


def test_every_sharded_search_op_lies_in_one_stage():
    script = r'''
import sys
import jax, jax.numpy as jnp
sys.path.insert(0, %r)
from test_spans import KW, stage_violations
from repro.core import hybrid_index as hi, sharded_index as shi
from repro.data import synthetic
c = synthetic.generate(seed=0, n_docs=900, n_queries=8, hidden=32,
                       vocab_size=512, n_topics=8)
idx = hi.build(jax.random.key(0), jnp.asarray(c.doc_emb),
               jnp.asarray(c.doc_tokens), c.vocab_size,
               codec="refine:sq8:4", **KW)
mesh = shi.make_shard_mesh(4)
sidx = shi.device_put(shi.partition(idx, 4), mesh)
fn = shi._compiled_search(mesh, shi.SHARD_AXIS, sidx.codec,
                          sidx.docs_per_shard, 4, 4, 10, False, False,
                          None, None)
rep = {"cluster_emb": sidx.cluster_sel.embeddings,
       "term_avg": sidx.term_sel.avg_scores, "codec": sidx.codec_params}
text = fn.lower(shi._shard_planes(sidx), rep, jnp.asarray(c.query_emb),
                jnp.asarray(c.query_tokens)).compile().as_text()
bad, n = stage_violations(text)
assert n > 0 and not bad, bad[:5]
assert "all-gather" in text or "all-reduce" in text
''' % os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.environ.get("PYTHONPATH", "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_scopes_change_nothing_but_metadata(corpus):
    """The stage scopes ride only in metadata: the compiled search with
    them stripped equals the program traced without them."""
    c = corpus
    idx = _index(c)
    args = (idx, jnp.asarray(c.query_emb), jnp.asarray(c.query_tokens))
    w = dict(kc=4, k2=4, top_r=10, use_kernel=False)
    def fresh():
        # a new function each time, so that jit traces it afresh
        def search(*a, **kw):
            return hi.search.__wrapped__(*a, **kw)
        return jax.jit(search, static_argnames=tuple(w))

    scoped = _compiled(fresh(), *args, **w)
    orig = stages._scope
    stages._scope = lambda stage: contextlib.nullcontext()
    try:
        plain = _compiled(fresh(), *args, **w)
    finally:
        stages._scope = orig
    assert "hi2." in scoped and "hi2." not in plain

    def strip(text):
        # the computations alone (not the tables of source locations
        # that head the text), without their metadata
        text = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
        return re.sub(r", metadata=\{[^}]*\}", "", text)

    assert strip(scoped) == strip(plain)


def test_span_nests_children_and_carries_attributes():
    spans.record()
    with spans.span("a", id=3):
        with spans.span("a.b"):
            pass
        with spans.span("a.c", k="v"):
            pass
    with spans.span("d"):
        pass
    got = spans.take()
    assert [s.name for s in got] == ["a", "a.b", "a.c", "d"]
    assert [s.parent for s in got] == [None, 0, 0, None]
    assert got[0].attrs == {"id": 3} and got[2].attrs == {"k": "v"}
    assert all(s.end_ns >= s.start_ns for s in got)
    assert got[0].start_ns <= got[1].start_ns and \
        got[2].end_ns <= got[0].end_ns
    assert spans.take() == []        # taken once; recording goes on
    assert spans.recording()


def test_span_keeps_nothing_when_recording_is_off():
    spans.stop()
    with spans.span("x", id=1):
        pass
    assert not spans.recording() and spans.take() == []

    @spans.span("f")
    def f(x):
        return x + 1

    spans.record()
    assert f(1) == 2 and f(2) == 3
    assert [s.name for s in spans.take()] == ["f", "f"]


def test_server_query_spans_and_results_unchanged(corpus):
    c = corpus
    server = serve.make_server(_index(c), serve.ServeConfig(
        kc=4, k2=4, top_r=10, max_batch=8))
    off = server.query(c.query_emb[:5], c.query_tokens[:5])
    spans.record()
    on = server.query(c.query_emb[:5], c.query_tokens[:5])
    got = spans.take()
    spans.stop()
    for a, b in zip(off[:3], on[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert [s.name for s in got] == ["hi2.query", "hi2.query.pad",
                                     "hi2.query.search", "hi2.query.split"]
    assert got[0].attrs == {"id": 5} and got[0].parent is None
    assert all(s.parent == 0 for s in got[1:])


def test_build_emits_its_phase_spans(corpus):
    c = corpus
    spans.record()
    _index(c)
    got = spans.take()
    names = [s.name for s in got]
    assert names == ["hi2.build", "hi2.build.kmeans",
                     "hi2.build.cluster_lists", "hi2.build.term_lists",
                     "hi2.build.codec", "hi2.build.codec.train",
                     "hi2.build.codec.encode"]
    assert got[0].parent is None and all(s.parent == 0 for s in got[1:5])
    assert got[5].parent == got[6].parent == 4
    inside = sum(s.end_ns - s.start_ns for s in got[1:5])
    assert inside <= got[0].end_ns - got[0].start_ns


@pytest.mark.parametrize("codec", ["opq", "sq8"])
def test_build_codec_spans_train_then_encode(corpus, codec):
    c = corpus
    spans.record()
    hi.build(jax.random.key(0), jnp.asarray(c.doc_emb),
             jnp.asarray(c.doc_tokens), c.vocab_size, codec=codec, **KW)
    got = spans.take()
    codec_at = [s.name for s in got].index("hi2.build.codec")
    children = [s for s in got if s.parent == codec_at]
    assert [s.name for s in children] == ["hi2.build.codec.train",
                                          "hi2.build.codec.encode"]
    train, encode = children
    outer = got[codec_at]
    assert outer.start_ns <= train.start_ns <= train.end_ns \
        <= encode.start_ns <= encode.end_ns <= outer.end_ns
