#!/usr/bin/env python3
"""Open-loop rate sweep on the chip: the highest rate a cell sustains.

    python3 bench/sweep.py --workload opq.online --seed 11 --seconds 20 \\
        --rates 20,40,60,80

Builds the cell once, then offers each rate in turn for ``--seconds``
through the cell's own traffic file with only ``rate_qps`` replaced,
and prints one JSON line per rate: requests, refusals, p50/p95/p99 of
the latency timed from when each request was due, the median latency
of the last tenth of the requests against the first tenth (a backlog
that grows over the window shows as a ratio well above 1), and rows
per executed batch.  A rate is sustained when nothing is refused and
the backlog does not grow.  The traffic file's ``rate_qps`` is then set
by hand, at about four fifths of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import numpy as np

    from bench import loops, run
    from bench.registry import Registry
    from bench.session import Session

    run.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    reg = Registry.load(ROOT)
    sess = Session(reg, args.workload, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(sess.traffic, rate_qps=rate)
        loop = loops.make(traffic, sess.server, sess.qe, sess.qt,
                          np.random.default_rng(args.seed))
        loop.warmup()
        try:
            loop.run(args.seconds)
        finally:
            loop.close()
        st = loop.stats
        lat = st["latency_s"]
        tenth = max(1, len(lat) // 10)
        head = np.nanmedian(lat[:tenth])
        tail = np.nanmedian(lat[-tenth:])
        print(json.dumps({
            "rate_qps": rate, "attempted": st["attempted"],
            "refused": st["refused"], "failed": st["failed"],
            "p50_ms": 1e3 * loops.percentile(lat, 50),
            "p95_ms": 1e3 * loops.percentile(lat, 95),
            "p99_ms": 1e3 * loops.percentile(lat, 99),
            "backlog_growth": float(tail / head) if head > 0 else None,
            "rows_per_step": st["served"] / max(st["batches"], 1),
            "gen_late_p99_ms": 1e3 * loops.percentile(st["late_s"], 99),
            "completed_qps": (st["attempted"] - st["failed"])
            / st["elapsed_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
