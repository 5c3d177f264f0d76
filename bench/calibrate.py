#!/usr/bin/env python3
"""Readings of the check's numbers for the program and for its control,
at a cell's own size, on several seeds, in one process.

    python3 bench/calibrate.py --workload sq8r.batch --seeds 1,2,3

For each seed: the cell's corpus and index; ``run.CHECK_SAMPLE`` queries
of the pool, drawn from the seed, answered by the program's timed path
(``Server.query`` at the cell's batch) and by the control, the plain
reference computed with bfloat16 inputs in its place;
both compared with the float64 reference by :func:`check.compare`.
Prints one JSON line per seed with ``score_err`` and ``rank_gap`` of
each.  The limits in the configuration files lie between the two.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def program_answers(sess, n: int) -> tuple:
    """``n`` queries of the pool drawn from the seed, answered by the
    program's timed path at the cell's batch: (pick, ids, scores)."""
    import numpy as np

    from bench import check

    pick = check.sample(len(sess.qe), n, sess.rng_check)
    b = sess.cfg["max_batch"]
    ids, scores = [], []
    for lo in range(0, len(pick), b):
        rows = pick[lo:lo + b]
        res = sess.server.query(sess.qe[rows], sess.qt[rows])
        ids.extend(np.asarray(res.doc_ids))
        scores.extend(np.asarray(res.scores))
    return pick, ids, scores


def readings(sess, n: int) -> dict:
    """score_err and rank_gap, worst over ``n`` queries, of the program
    and of the control, each against the float64 reference."""
    from bench import check
    from bench.reference import Reference

    pick, prog_ids, prog_scores = program_answers(sess, n)
    trained = sess.trained()
    sess.free()
    planes = sess.planes(trained)
    ref = Reference(trained, planes, sess.cfg, sess.rows())
    ctl = Reference(trained, planes, sess.cfg, sess.rows(),
                    precision="bfloat16")
    out = {"program": [0.0, 0.0], "control": [0.0, 0.0]}
    for k, q in enumerate(pick):
        opts = ref.options(sess.qe[q], sess.qt[q])
        for who, (ids, scores) in (
                ("program", (prog_ids[k], prog_scores[k])),
                ("control", ctl.control_answer(sess.qe[q], sess.qt[q]))):
            err, gap = check.compare(ids, scores, opts)
            worst = out[who]
            worst[0] = max(worst[0], err)
            worst[1] = max(worst[1], gap)
    return {**{who: {"score_err": v[0], "rank_gap": v[1]}
               for who, v in out.items()},
            "reference_open": ref.n_ambiguous()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="allow the CPU (for a rehearsal at a tiny size)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import run
    from bench.registry import Registry
    from bench.session import Session

    run.enable_compile_cache()
    if jax.devices()[0].platform != "tpu" and not args.cpu:
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    reg = Registry.load(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        sess = Session(reg, args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings(sess, run.CHECK_SAMPLE)}),
              flush=True)
        del sess               # the next seed's corpus needs the memory
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
