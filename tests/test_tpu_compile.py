"""Compile-only checks against a described (not attached) TPU v5e.

Interpret mode runs a Pallas kernel's semantics on the CPU but not the
TPU compiler's rules: block-shape tiling, memory layouts, VMEM and HBM
limits.  These tests lower and compile the search path's kernels and
the k-means assignment kernel at the ``hi2-synth/serve_msmarco`` widths
(``configs/hi2_synth.py``) for one v5e chip, plus the XLA serving step
at the batch the chip's HBM holds, the build's OPQ encode of one chip's
corpus, and the names and stages of the fused kernels in the compiled
program.
Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture — never at
import — because only one process at a time may load the TPU library:
every xdist worker collects these tests, and only the worker that runs
them loads it.  The persistent compilation cache is off around them (a
compile for a described chip can be written to it but never read back).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import hi2_synth
from repro.core import hybrid_index as hi
from repro.core.codecs import pq
from repro.kernels.assign_topk import kernel as at_kernel
from repro.kernels.pq_adc import kernel as adc_kernel
from repro.kernels.sq8_dot import kernel as sq8_kernel
from repro.launch import cells

#: serve_msmarco widths; the batch is the one-chip max_batch (B=256 does
#: not fit a v5e's HBM on the XLA path)
SHAPE = dataclasses.replace(hi2_synth.HI2ServeShape("serve_msmarco"),
                            query_batch=64)
N_DOCS = 1 << 20
B = SHAPE.query_batch
C = SHAPE.kc * SHAPE.cluster_capacity + SHAPE.k2 * SHAPE.term_capacity


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "absent"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_pq_adc_fused_compiles_at_msmarco_widths(one_chip):
    m, k = SHAPE.pq_m, SHAPE.pq_k
    _, text = _compile(
        lambda *a: adc_kernel.pq_adc_fused(*a, interpret=False),
        _sds(one_chip, (B, m, k), jnp.float32),
        _sds(one_chip, (N_DOCS, 128), jnp.uint8),     # m=96 lane-padded
        _sds(one_chip, (B, C), jnp.int32),
        _sds(one_chip, (B, C), jnp.int32))
    assert "tpu_custom_call" in text


def test_sq8_dot_fused_compiles_at_msmarco_widths(one_chip):
    h = SHAPE.hidden
    _, text = _compile(
        lambda *a: sq8_kernel.sq8_dot_fused(*a, interpret=False),
        _sds(one_chip, (B, h), jnp.float32),
        _sds(one_chip, (N_DOCS, h), jnp.uint8),
        _sds(one_chip, (B, C), jnp.int32),
        _sds(one_chip, (B, C), jnp.int32))
    assert "tpu_custom_call" in text


def test_topk_scores_compiles_at_msmarco_widths(one_chip):
    h, l_blk = SHAPE.hidden, 512
    l_pad = -(-SHAPE.n_clusters // l_blk) * l_blk
    _, text = _compile(
        lambda x, e: at_kernel.topk_scores(
            x, e, k=SHAPE.kc, n_blk=B, l_blk=l_blk,
            l_true=SHAPE.n_clusters, interpret=False),
        _sds(one_chip, (B, h), jnp.float32),
        _sds(one_chip, (l_pad, h), jnp.float32))
    assert "tpu_custom_call" in text


def test_assign_argmax_compiles_at_msmarco_widths(one_chip):
    h, l_blk = SHAPE.hidden, 512
    l_pad = -(-SHAPE.n_clusters // l_blk) * l_blk
    _, text = _compile(
        lambda x, c: at_kernel.assign_argmax(x, c, n_blk=256, l_blk=l_blk,
                                             interpret=False),
        _sds(one_chip, (4096, h), jnp.float32),
        _sds(one_chip, (l_pad, h), jnp.float32))
    assert "tpu_custom_call" in text


def test_xla_search_step_fits_one_chip(one_chip):
    index = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        cells._hi2_abstract_index(
            dataclasses.replace(SHAPE, n_docs=N_DOCS)))
    compiled, text = _compile(
        lambda idx, qe, qt: hi.search(idx, qe, qt, kc=SHAPE.kc,
                                      k2=SHAPE.k2, top_r=SHAPE.top_r),
        index,
        _sds(one_chip, (B, SHAPE.hidden), jnp.float32),
        _sds(one_chip, (B, SHAPE.query_len), jnp.int32))
    assert "tpu_custom_call" not in text        # the XLA path runs no kernel
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 15.75e9, f"{used / 1e9:.2f} GB exceeds one v5e's HBM"


def test_opq_encode_block_compiles_small_at_full_precision(one_chip):
    """The program the build's OPQ encode calls once per block of rows
    (2^20 docs take 256 calls), in the default context as the build
    calls it: no rotated (n, h) f32 copy of the corpus (3.2 GB at 2^20
    rows) among its temporaries, and every product at the highest
    operand precision."""
    h, m, k = SHAPE.hidden, SHAPE.pq_m, SHAPE.pq_k
    opq = pq.OPQCodebook(
        rotation=_sds(one_chip, (h, h), jnp.float32),
        codebook=pq.PQCodebook(
            codewords=_sds(one_chip, (m, k, h // m), jnp.float32)))
    compiled, text = _compile(
        pq.opq_encode_block, opq,
        _sds(one_chip, (pq.ENCODE_BLOCK, h), jnp.float32))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1e9, f"{temp / 1e9:.2f} GB of temporaries"
    dots = [line for line in text.splitlines()
            if re.search(r"= \S+ (?:convolution|dot)\(", line)]
    assert dots
    for line in dots:
        assert "operand_precision={highest,highest}" in line, line


def _custom_calls(text):
    """(instruction name, op_name) of each Pallas call of a program."""
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)", line).group(1)
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((name, op.group(1) if op else ""))
    return out


def test_fused_kernels_keep_their_names(one_chip):
    """Each fused Pallas call is named explicitly: the name of its
    instruction in the program and in a profiler trace."""
    h, m, k = SHAPE.hidden, SHAPE.pq_m, SHAPE.pq_k
    n, c = 4096, 1024
    cases = [
        ("sq8_dot_fused", lambda *a: sq8_kernel.sq8_dot_fused(
            *a, interpret=False),
         [(B, h), (n, h), (B, c), (B, c)],
         [jnp.float32, jnp.uint8, jnp.int32, jnp.int32]),
        ("pq_adc_fused", lambda *a: adc_kernel.pq_adc_fused(
            *a, interpret=False),
         [(B, m, k), (n, 128), (B, c), (B, c)],
         [jnp.float32, jnp.uint8, jnp.int32, jnp.int32]),
        ("topk_scores", lambda x, e: at_kernel.topk_scores(
            x, e, k=SHAPE.kc, n_blk=B, l_blk=512, l_true=1000,
            interpret=False),
         [(B, h), (1024, h)], [jnp.float32, jnp.float32]),
    ]
    for kernel, fn, shapes, dtypes in cases:
        _, text = _compile(fn, *(_sds(one_chip, s, d)
                                 for s, d in zip(shapes, dtypes)))
        names = [name for name, _ in _custom_calls(text)]
        assert names and all(nm.startswith(kernel + ".") or nm == kernel
                             for nm in names), (kernel, names)


def test_sq8_search_kernels_lie_in_their_stages(one_chip, monkeypatch):
    """The served sq8 + refine step with the fused path: the scoring
    kernel is under ``hi2.score``, the dispatch top-k under
    ``hi2.dispatch``."""
    from repro.kernels.assign_topk import ops as at_ops
    from repro.kernels.sq8_dot import ops as sq8_ops

    for mod in (at_ops, sq8_ops):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    shape = dataclasses.replace(SHAPE, n_docs=1 << 14, codec="refine:sq8:4")
    index = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         cells._hi2_abstract_index(shape))
    jax.clear_caches()       # no trace made with the interpreter is reused
    try:
        _, text = _compile(
            lambda idx, qe, qt: hi.search(idx, qe, qt, kc=shape.kc,
                                          k2=shape.k2, top_r=shape.top_r,
                                          use_kernel=True),
            index, _sds(one_chip, (B, shape.hidden), jnp.float32),
            _sds(one_chip, (B, shape.query_len), jnp.int32))
    finally:
        jax.clear_caches()
    stage = {name.rsplit(".", 1)[0]: re.findall(r"hi2\.(\w+)", op)
             for name, op in _custom_calls(text)}
    assert stage == {"sq8_dot_fused": ["score"], "topk_scores": ["dispatch"]}


def test_dedup_scope_of_search_step_has_no_gather(one_chip):
    """The served step's ``hi2.dedup`` scope is two sorts and no gather:
    a gather over the 63,488 candidate slots of a B=64 batch costs the
    v5e ~42 ms, ~10x the sorts that can carry the same values."""
    shape = dataclasses.replace(SHAPE, n_docs=1 << 14)
    index = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         cells._hi2_abstract_index(shape))
    _, text = _compile(
        lambda idx, qe, qt: hi.search(idx, qe, qt, kc=shape.kc,
                                      k2=shape.k2, top_r=shape.top_r),
        index, _sds(one_chip, (B, shape.hidden), jnp.float32),
        _sds(one_chip, (B, shape.query_len), jnp.int32))
    dedup = [line for line in text.splitlines()
             if re.search(r'op_name="[^"]*hi2\.dedup', line)]
    ops = [re.search(r"= .*? ([a-z][\w-]*)\(", line) for line in dedup]
    ops = [m.group(1) for m in ops if m]
    assert ops.count("sort") == 2, ops
    assert not [op for op in ops if op in ("gather", "scatter")], ops
