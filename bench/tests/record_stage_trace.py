#!/usr/bin/env python3
"""Records the small trace that tests/test_bench_scopes.py reduces by
stage.

    python3 bench/tests/record_stage_trace.py OUT_DIR    # on one TPU chip

Builds the tiny ``tiny-sq8r`` fixture index with the program's span
recorder on, warms its B=8 step, then traces a window of 50 ms of
closed-loop steps, and writes ``OUT_DIR/tiny_sq8r.xplane.pb`` and,
beside it, ``OUT_DIR/tiny_sq8r.stages.json``: the stage of each
instruction of the compiled search program (:mod:`bench.scopes`).
Prints the reduction, the stage milliseconds and the host spans kept.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import loops, scopes, trace_reduce
    from bench.registry import Registry
    from bench.session import Session
    from repro import spans

    spans.record()
    spec = {"workloads": [{"name": "tiny", "config": "tiny-sq8r",
                           "traffic": "batch8", "chips": 1}]}
    reg = Registry(spec, HERE / "fixtures")
    sess = Session(reg, "tiny", 3)
    loop = loops.make(sess.traffic, sess.server, sess.qe, sess.qt,
                      sess.rng_load)
    loop.warmup()
    setup_spans = spans.take()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        loop.run(0.05)
        jax.profiler.stop_trace()
        window_spans = spans.take()
        src = trace_reduce.find_xplane(Path(tmp))
        Path(out).mkdir(parents=True, exist_ok=True)
        dst = Path(out) / "tiny_sq8r.xplane.pb"
        shutil.copy(src, dst)
    smap = scopes.cell_stage_map(sess.cfg)
    (Path(out) / "tiny_sq8r.stages.json").write_text(
        json.dumps(smap, indent=0, sort_keys=True) + "\n")
    red = trace_reduce.reduce(trace_reduce.load(dst))
    n, step = trace_reduce.module_stats(red, scopes.MODULE)
    by_stage = scopes.stage_seconds(red, smap) or {}
    queries = [s for s in window_spans if s.name == "hi2.query"]
    print(json.dumps({
        "bytes": dst.stat().st_size,
        **{k: red[k] for k in ("window_s", "busy_s", "idle_share",
                               "modules", "gaps_by_span")},
        "step_ms": 1e3 * step / n if n else None,
        "stage_ms": {k: 1e3 * v / n for k, v in by_stage.items()} if n
        else None,
        "build_spans_s": {s.name: (s.end_ns - s.start_ns) / 1e9
                          for s in setup_spans
                          if s.name.startswith("hi2.build")},
        "query_spans": len(queries),
        "query_host_ms": (sum(s.end_ns - s.start_ns for s in queries)
                          / len(queries) / 1e6) if queries else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
