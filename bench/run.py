#!/usr/bin/env python3
"""Chip benchmark of HI² serving: one run of one cell of BENCHMARK.json.

    python3 bench/run.py --workload sq8r.batch --seed 7 --seconds 30 \\
        --trace 0

One run, in one process, on the chip it is started on:

  1. the cell's corpus and query pool, generated on the device from
     ``--seed`` (:mod:`bench.corpus`);
  2. the index, built by the program (``hybrid_index.build``);
  3. the cell's programs warmed: the B=64 step of a closed loop, or the
     runtime's bucket ladder of an open loop (:mod:`bench.loops`);
  4. ``--seconds`` of traffic; with ``--trace 1`` under the profiler,
     reduced by :mod:`bench.trace_reduce`;
  5. recall@R of every answer of the window against the exact oracle
     (:mod:`bench.oracle`), and the check of ``correct``: every answer
     against the result contract, a sample drawn from the seed against
     the plain reference, which builds its own lists and codes from the
     corpus (:mod:`bench.reference`, :mod:`bench.derive`,
     :mod:`bench.check`);
  6. one JSON line: ``correct``, ``attempted``, ``failed``, ``metrics``
     (the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
     metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
     ``checks``: each compared number beside its limit, which also end
     the standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 1
and prints no result.  JAX's persistent compilation cache is kept
where ``JAX_COMPILATION_CACHE_DIR`` says, or else in ``bench/.cache/jax``
inside the checkout, so only the first run of a cell there compiles.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / "bench" / ".cache" / "jax"
#: answers compared with the plain reference, drawn from the seed
CHECK_SAMPLE = 64


def enable_compile_cache() -> Path:
    """JAX's persistent compilation cache, for every program however
    short to compile: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`CACHE_DIR`.  Returns the directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = Path(env) if env else CACHE_DIR
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def process_start() -> float:
    """Wall-clock time at which this process started."""
    try:
        stat = Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        btime = next(int(line.split()[1]) for line in
                     Path("/proc/stat").read_text().splitlines()
                     if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def finite(x):
    return x if math.isfinite(x) else None


def check_answers(sess, served, ref) -> tuple:
    """(worst score_err, worst rank_gap) over a sample of the window's
    answers drawn from the seed."""
    from bench import check

    have = [i for i, ids in enumerate(served.ids) if ids is not None]
    worst_err, worst_gap = 0.0, 0.0
    for i in check.sample(len(have), CHECK_SAMPLE, sess.rng_check):
        a = have[i]
        q = served.query[a]
        err, gap = check.compare(served.ids[a], served.scores[a],
                                 ref.options(sess.qe[q], sess.qt[q]))
        worst_err = max(worst_err, err)
        worst_gap = max(worst_gap, gap)
    return worst_err, worst_gap


def recall(sess, served) -> tuple:
    """Mean recall@R of every request of the window against the exact
    oracle (a missing answer reads 0), and the oracle's rescans."""
    import numpy as np

    from bench import oracle

    r = sess.cfg["top_r"]
    exact, rescans = oracle.exact_topk(sess.qe[served.query],
                                       sess.data.doc_emb, r)
    hits = [0.0 if ids is None else
            len(set(ids.tolist()) & set(exact[j].tolist())) / r
            for j, ids in enumerate(served.ids)]
    return float(np.mean(hits)), rescans


def run_cell(reg, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict) -> tuple:
    """One run of ``workload``; returns (result, checks, diagnostics)."""
    import jax
    import numpy as np

    from bench import check, loops, trace_reduce
    from bench.reference import Reference
    from bench.session import Counters, Session

    counters = Counters()
    sess = Session(reg, workload, seed)
    cfg = sess.cfg
    loop = loops.make(sess.traffic, sess.server, sess.qe, sess.qt,
                      sess.rng_load)
    loop.warmup()
    compile_s = counters.compile_seconds
    setup = {"compiles": counters.compiles, "cache_hits": counters.hits,
             "cache_misses": counters.misses}

    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    setup_s = time.time() - t_start
    before = counters.compiles
    try:
        served = loop.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        close = getattr(loop, "close", None)
        if close is not None:
            close()
    window_compiles = counters.compiles - before
    memory_peak = sess.peak_bytes()
    red = None
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(Path(tmp.name))))
        tmp.cleanup()

    # the program's trained parameters are read back, and its state is
    # freed before the checks
    trained = sess.trained()
    stats = dict(loop.stats)
    del loop
    sess.free()
    gc.collect()

    t0 = time.perf_counter()
    recall_at_r, rescans = recall(sess, served)
    t1 = time.perf_counter()
    ref = Reference(trained, sess.planes(trained), cfg, sess.rows())
    t2 = time.perf_counter()
    worst_err, worst_gap = check_answers(sess, served, ref)
    t3 = time.perf_counter()
    violations = check.contract_violations(served.ids, served.scores,
                                           cfg["top_r"], cfg["n_docs"])
    limits = {"contract": 0, "compiles_in_window": 0,
              "score_err": cfg["limits"]["score_err"],
              "rank_gap": cfg["limits"]["rank_gap"]}
    correct, checks = check.judge(
        {"contract": violations, "compiles_in_window": window_compiles,
         "score_err": worst_err, "rank_gap": worst_gap}, limits)

    end_to_end = {"setup_s": setup_s, "recall_at_100": recall_at_r}
    if "qps" in stats:
        end_to_end["qps"] = stats["qps"]
    if "latency_s" in stats:
        end_to_end["p95_ms"] = 1e3 * loops.percentile(stats["latency_s"], 95)
        end_to_end["p50_ms"] = 1e3 * loops.percentile(stats["latency_s"], 50)
    devices = sess.devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    live = int(served.n_candidates[served.n_candidates >= 0].sum())
    run = {"build_s": sess.build_s, "compile_s": compile_s, "stats": stats,
           "live_candidates": live, "hbm_peak_bytes": memory_peak}
    ctx = SimpleNamespace(trace=red, run=run, cfg=cfg, peaks=peaks,
                          workload=workload)
    if trace:
        metrics = reg.read_layer(workload, ctx)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    else:
        # a tail past the share of missing requests reads null
        metrics = {m["name"]: {"value": finite(end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in reg.end_to_end(workload)}
    result = {"correct": correct, "attempted": stats["attempted"],
              "failed": stats["failed"], "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(red)
    result["checks"] = checks
    diag = {"data_s": sess.data_s, "build_s": sess.build_s,
            "compile_s": compile_s, **setup, "oracle_s": t1 - t0,
            "reference_planes_s": t2 - t1, "reference_s": t3 - t2,
            "reference_open": ref.n_ambiguous(),
            "window_compiles": window_compiles, "oracle_rescans": rescans,
            "live_candidates": live,
            "end_to_end": end_to_end,
            "stats": {k: v for k, v in stats.items()
                      if not isinstance(v, np.ndarray)}}
    if trace:
        diag["trace"] = {k: red[k] for k in ("categories", "modules",
                                             "gaps_by_span", "n_devices")}
    return result, checks, diag


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program next to the benchmark "
              f"({ROOT / 'src' / 'repro'} is missing)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    from bench.registry import Registry, UnknownDevice

    reg = Registry.load(ROOT)
    cell = reg.workload(args.workload)

    import jax
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU; JAX found {devices[0].platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chip(s); "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    try:
        peaks = reg.peaks(devices[0].device_kind)
    except UnknownDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    result, checks, diag = run_cell(reg, args.workload, args.seed,
                                    args.seconds, bool(args.trace), t_start,
                                    peaks)
    print(json.dumps(diag, default=float), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
