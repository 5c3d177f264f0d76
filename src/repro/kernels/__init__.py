"""Pallas TPU kernels for the framework's compute hot spots.

    pq_adc          — PQ asymmetric-distance scoring (paper Eq. 4), the
                      per-query candidate-evaluation hot path of HI²;
                      includes the fused gather+ADC+mask search path
                      (DESIGN.md §11).
    sq8_dot         — fused gather+dequantized-dot scoring for the sq8
                      codec (DESIGN.md §11).
    assign_topk     — fused embedding×centroid scoring with running
                      argmax: KMeans assignment + cluster dispatch
                      (paper Eq. 6) over large L; ``topk_scores`` is
                      the lax.top_k-exact dispatch top-k (§11).
    flash_attention — SWA/GQA-capable flash attention for the LM-family
                      architecture backbones (beyond-paper optimization).

Every kernel ships ``kernel.py`` (pl.pallas_call + explicit BlockSpec
VMEM tiling), ``ops.py`` (jit'd public wrapper whose ``interpret`` flag
comes from :func:`interpret_mode`, so CPU CI exercises the kernel
body), and ``ref.py`` (pure-jnp oracle used by the tests'
assert_allclose sweeps).  ``row_gather`` is the fused scorers' shared
in-kernel DMA row gather.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """The one compile-or-interpret decision for every Pallas kernel:
    compile for the TPU, interpret on the CPU backend (tests, CI), and
    refuse anything else — a kernel must never fall back to the
    interpreter on a device it was not written for."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on a TPU or interpreted on the "
        f"CPU backend; the default backend here is {backend!r}")
