"""Kernel benchmark: fused vs unfused vs oracle on the scoring hot path
(DESIGN.md §11).

    PYTHONPATH=src python benchmarks/kernel_bench.py --smoke --check \\
        --out results/BENCH_kernels.json                            # CI
    PYTHONPATH=src python benchmarks/kernel_bench.py                # full

Three comparisons, each at the same candidate shapes the search engine
produces:

  · ``pq_adc``: the fused gather+ADC kernel (``pq_adc_fused`` — gathers
    the (N, m) resident plane in-kernel, masks in-kernel) against the
    unfused kernel path (XLA gather to (B, C, m) then the ADC kernel)
    and the pure-jnp oracle;
  · ``sq8_dot``: the fused gather+dequantized-dot kernel against the
    unfused einsum path;
  · ``assign_topk``: the running-top-k dispatch kernel against
    ``lax.top_k`` over the full score plane.

Timing fields follow the ``check_regression`` naming convention
(``us_per_call`` lower-better, ``qps_candidates`` higher-better) so the
gate treats them directionally; the parity fields (``matches_ref``,
``ids_bit_identical``) are deterministic booleans gated bit-exactly.
On CPU the kernels run in interpret mode — absolute numbers measure the
interpreter, not TPU silicon; the gate only catches order-of-magnitude
rot and parity breaks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time_call(fn, *a, warmup=1, iters=3) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*a))
    return (time.perf_counter() - t0) / iters * 1e6  # µs per call


def _allclose(a, b, tol=1e-3) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if not (np.isinf(a) == np.isinf(b)).all():
        return False
    fin = np.isfinite(a)
    return bool(np.allclose(a[fin], b[fin], atol=tol, rtol=tol))


def _bench_pq_adc(b, m, k, n, c, c_blk) -> dict:
    from repro.kernels.pq_adc import ops, ref

    key = jax.random.key(0)
    lut = jax.random.normal(key, (b, m, k), jnp.float32)
    plane = jax.random.randint(jax.random.fold_in(key, 1), (n, m),
                               0, k).astype(jnp.uint8)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (b, c),
                             0, n, jnp.int32)
    live = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.9,
                                (b, c)).astype(jnp.int32)

    fused = lambda: ops.pq_adc_fused(lut, plane, ids, live,   # noqa: E731
                                     c_blk=c_blk)

    def unfused():
        codes = plane[ids].astype(jnp.int32)        # (B, C, m) in HBM
        return jnp.where(live.astype(bool), ops.pq_adc(lut, codes),
                         -jnp.inf)

    unfused = jax.jit(unfused)
    oracle = jax.jit(lambda: ref.pq_adc_fused(lut, plane, ids, live))

    want = oracle()
    us_f = _time_call(fused)
    us_u = _time_call(unfused)
    us_r = _time_call(oracle)
    cands = b * c
    return {
        "shape": {"B": b, "m": m, "k": k, "N": n, "C": c, "c_blk": c_blk},
        "fused_us_per_call": round(us_f, 1),
        "unfused_us_per_call": round(us_u, 1),
        "ref_us_per_call": round(us_r, 1),
        "qps_candidates_fused": round(cands / us_f * 1e6, 0),
        "qps_candidates_unfused": round(cands / us_u * 1e6, 0),
        "fused_matches_ref": _allclose(fused(), want),
        "unfused_matches_ref": _allclose(unfused(), want),
    }


def _bench_sq8(b, h, n, c, c_blk) -> dict:
    from repro.kernels.sq8_dot import ops, ref

    key = jax.random.key(1)
    q = jax.random.normal(key, (b, h), jnp.float32)
    plane = jax.random.randint(jax.random.fold_in(key, 1), (n, h),
                               0, 256).astype(jnp.uint8)
    ids = jax.random.randint(jax.random.fold_in(key, 2), (b, c),
                             0, n, jnp.int32)
    live = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.9,
                                (b, c)).astype(jnp.int32)

    fused = lambda: ops.sq8_dot_fused(q, plane, ids, live,    # noqa: E731
                                      c_blk=c_blk)

    def unfused():
        rows = plane[ids].astype(jnp.float32)       # (B, C, h) in HBM
        return jnp.where(live.astype(bool),
                         jnp.einsum("bh,bch->bc", q, rows), -jnp.inf)

    unfused = jax.jit(unfused)
    want = ref.sq8_dot_fused(q, plane, ids, live)
    us_f = _time_call(fused)
    us_u = _time_call(unfused)
    cands = b * c
    return {
        "shape": {"B": b, "h": h, "N": n, "C": c, "c_blk": c_blk},
        "fused_us_per_call": round(us_f, 1),
        "unfused_us_per_call": round(us_u, 1),
        "qps_candidates_fused": round(cands / us_f * 1e6, 0),
        "qps_candidates_unfused": round(cands / us_u * 1e6, 0),
        "fused_matches_ref": _allclose(fused(), want),
    }


def _bench_topk(b, l, h, k) -> dict:
    from repro.kernels.assign_topk import ops, ref

    key = jax.random.key(2)
    x = jax.random.normal(key, (b, h), jnp.float32)
    emb = jax.random.normal(jax.random.fold_in(key, 1), (l, h),
                            jnp.float32)

    fused = lambda: ops.topk_scores(x, emb, k)                # noqa: E731
    unfused = jax.jit(lambda: ref.topk_scores(x, emb, k))

    ws, wi = unfused()
    gs, gi = fused()
    us_f = _time_call(fused)
    us_u = _time_call(unfused)
    return {
        "shape": {"B": b, "L": l, "h": h, "k": k},
        "fused_us_per_call": round(us_f, 1),
        "unfused_us_per_call": round(us_u, 1),
        "ids_bit_identical": bool(np.array_equal(np.asarray(wi),
                                                 np.asarray(gi))),
        "scores_match": _allclose(gs, ws, tol=1e-5),
    }


def run(args) -> dict:
    if args.smoke:
        adc = _bench_pq_adc(b=8, m=4, k=64, n=4000, c=512, c_blk=128)
        sq8 = _bench_sq8(b=8, h=32, n=4000, c=512, c_blk=128)
        topk = _bench_topk(b=8, l=128, h=32, k=6)
    else:
        adc = _bench_pq_adc(b=64, m=8, k=256, n=100_000, c=2048, c_blk=256)
        sq8 = _bench_sq8(b=64, h=64, n=100_000, c=2048, c_blk=256)
        topk = _bench_topk(b=64, l=1024, h=64, k=6)

    failures = []
    for name, rep, keys in (
            ("pq_adc", adc, ("fused_matches_ref", "unfused_matches_ref")),
            ("sq8_dot", sq8, ("fused_matches_ref",)),
            ("assign_topk", topk, ("ids_bit_identical", "scores_match"))):
        for kf in keys:
            if not rep[kf]:
                failures.append(f"{name}.{kf} is False")

    from repro import kernels

    return {
        "bench": "kernels",
        "smoke": bool(args.smoke),
        "backend": jax.default_backend(),
        "interpret_mode": kernels.interpret_mode(),
        "pq_adc": adc,
        "sq8_dot": sq8,
        "assign_topk": topk,
        "check_failures": failures,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="CI-sized shapes")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if any kernel disagrees with its "
                         "oracle")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)

    report = run(args)
    text = json.dumps(report, indent=1, sort_keys=True)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.check and report["check_failures"]:
        sys.exit("kernel parity violated: "
                 + "; ".join(report["check_failures"]))


if __name__ == "__main__":
    main()
