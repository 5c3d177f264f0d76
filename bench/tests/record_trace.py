#!/usr/bin/env python3
"""Records the small trace that tests/test_bench_trace.py reduces.

    python3 bench/tests/record_trace.py OUT_DIR     # on one TPU chip

Builds the tiny ``tiny-opq`` fixture index, warms its B=8 step, then
traces a window of 50 ms of closed-loop steps under the harness's host spans
(``bench.window``, ``bench.call``, ``bench.read``) and copies the
``.xplane.pb`` to ``OUT_DIR/tiny_opq.xplane.pb``.
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

    from bench import loops, trace_reduce
    from bench.registry import Registry
    from bench.session import Session

    spec = {"workloads": [{"name": "tiny", "config": "tiny-opq",
                           "traffic": "batch8", "chips": 1}]}
    reg = Registry(spec, HERE / "fixtures")
    sess = Session(reg, "tiny", 3)
    loop = loops.make(sess.traffic, sess.server, sess.qe, sess.qt,
                      sess.rng_load)
    loop.warmup()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        loop.run(0.05)
        jax.profiler.stop_trace()
        src = trace_reduce.find_xplane(Path(tmp))
        Path(out).mkdir(parents=True, exist_ok=True)
        dst = Path(out) / "tiny_opq.xplane.pb"
        shutil.copy(src, dst)
    red = trace_reduce.reduce(trace_reduce.load(dst))
    print(json.dumps({"bytes": dst.stat().st_size,
                      **{k: red[k] for k in ("window_s", "busy_s",
                                             "idle_share", "modules",
                                             "gaps_by_span")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
