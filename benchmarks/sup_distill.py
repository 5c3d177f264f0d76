"""Supervised HI² distillation benchmark (paper §4.3, DESIGN.md §15):
the selector-quality evidence chain for HI²_sup.

    PYTHONPATH=src python benchmarks/sup_distill.py --smoke --check \\
        --out results/BENCH_sup.json                              # CI
    PYTHONPATH=src python benchmarks/sup_distill.py               # full

Two stages, each in its own subprocess (this orchestrator never touches
JAX — a chip belongs to one process):

  · **train + sweep**: build the HI²_unsup baseline, mine
    its top-scoring non-relevant docs as hard negatives (union with the
    topic-matched pool), train the supervised selectors with in-batch
    negatives and the refine-stage KL (§15 recipe), assemble HI²_sup at
    the frozen training-time φ, and sweep recall@R against the unsup
    index over the shared ``frontier.WIDTH_GRID`` operating points —
    matched capacities make ``candidate_cost`` *identical* at every
    (kc, k2), so any recall delta is pure selector quality.  The sup
    index is also round-tripped through ``save_index``/
    ``restore_index`` and compared plane-by-plane.
  · **variants** (subprocess, 2 emulated devices): the trained
    ``SupSelectors`` bundle drives all four serving layouts (plain /
    sharded / mutable / sharded-mutable) to bit-identical doc ids, and
    a supervised *mutable* index survives add → delete → compact with
    the compaction bit-identical to a from-scratch supervised build
    over the survivors.

``--check`` enforces the §15 acceptance contracts: (a) sup recall >=
unsup at matched cost on at least one operating point (costs asserted
equal), (b) the loss trajectory is monotone-ish (windowed means
decrease), (c) the index round-trip is bit-identical, (d) all four
layouts agree and the mutable lifecycle holds.  Every report field is
deterministic (losses rounded to 4dp, no wall-clock), so the
regression gate compares bit-exactly.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

LAYOUTS = ("plain", "sharded", "mutable", "sharded_mutable")
CODEC = "opq"

#: oracle width (see benchmarks/autotune.py): recall@top_r of the exact
#: top-10 neighbors — the teacher's own objective (Eq. 10), so the
#: sweep measures exactly what distillation optimizes
ORACLE_WIDTH = 10

#: fraction of consecutive loss windows whose mean must improve on the
#: previous window for the trajectory to count as monotone-ish
MONOTONE_FRAC = 0.7


def _scale(args) -> None:
    if args.smoke:
        args.docs, args.queries = 2500, 192
        args.hidden, args.vocab, args.clusters = 32, 2048, 32
        args.pq_m, args.pq_k, args.kmeans_iters = 4, 64, 6
        args.steps = args.steps or 160
    else:
        args.docs, args.queries = 4000, 256
        args.hidden, args.vocab, args.clusters = 32, 2048, 32
        args.pq_m, args.pq_k, args.kmeans_iters = 8, 64, 8
        args.steps = args.steps or 300


def _common(args) -> dict:
    return dict(k1_terms=8, codec=CODEC, pq_m=args.pq_m, pq_k=args.pq_k,
                cluster_capacity=512, term_capacity=96)


def _corpus(args):
    from repro.data import synthetic
    return synthetic.generate(seed=0, n_docs=args.docs,
                              n_queries=args.queries, hidden=args.hidden,
                              vocab_size=args.vocab,
                              n_topics=args.clusters)


def _cfg(args, n_steps=None):
    from repro.launch import train as tr
    return tr.SupTrainConfig(
        n_clusters=args.clusters, encoder_layers=1,
        encoder_dim=args.hidden, encoder_heads=2,
        n_steps=args.steps if n_steps is None else n_steps,
        batch_queries=32, n_negatives=7, n_inbatch=4, refine_weight=0.5,
        lr=2e-3, kmeans_iters=args.kmeans_iters, seed=0)


def _tree_equal(a, b) -> bool:
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# --------------------------------------------------------------------------
# stage: train + sweep (subprocess)
# --------------------------------------------------------------------------

def run_train_sweep(args, ckpt_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import checkpoint as ckpt
    from repro.core import distill, hybrid_index as hi, metrics
    from repro.core.exec import frontier
    from repro.data import synthetic
    from repro.launch import train as tr, tune

    corpus = _corpus(args)
    common = _common(args)
    qe, qt = jnp.asarray(corpus.query_emb), jnp.asarray(corpus.query_tokens)
    oracle = tune.exact_oracle(corpus.doc_emb, corpus.query_emb,
                               ORACLE_WIDTH)

    unsup = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
                     jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                     n_clusters=args.clusters,
                     kmeans_iters=args.kmeans_iters, **common)

    # §15 negative pool: topic-matched ∪ mined-from-the-unsup-index
    topic = synthetic.hard_negatives(corpus, 7, seed=0)
    mined = distill.mine_hard_negatives(unsup, corpus.query_emb,
                                        corpus.query_tokens, corpus.qrels,
                                        7)
    pool = np.concatenate([topic, mined], axis=1)

    cfg = _cfg(args)
    params, enc_cfg, assign, losses = tr.train_hi2_sup(
        corpus, cfg, log_every=0, negatives=pool)
    ckpt.save(ckpt_dir, cfg.n_steps, {"params": params})

    sup = tr.build_sup_index(corpus, params, enc_cfg, assign, **common)

    points, wins = [], 0
    for kc, k2 in frontier.WIDTH_GRID:
        ru = hi.search(unsup, qe, qt, kc=kc, k2=k2, top_r=args.top_r)
        rs = hi.search(sup, qe, qt, kc=kc, k2=k2, top_r=args.top_r)
        cost_u = hi.candidate_cost(unsup, kc, k2, args.top_r)
        cost_s = hi.candidate_cost(sup, kc, k2, args.top_r)
        r_u = round(float(tune.per_query_recall(
            ru.doc_ids, oracle, args.top_r).mean()), 4)
        r_s = round(float(tune.per_query_recall(
            rs.doc_ids, oracle, args.top_r).mean()), 4)
        wins += r_s >= r_u
        points.append({
            "kc": kc, "k2": k2,
            "cost_unsup": int(cost_u), "cost_sup": int(cost_s),
            "recall_unsup": r_u, "recall_sup": r_s,
            "qrels_recall_unsup": round(metrics.recall_at_k(
                ru.doc_ids, corpus.qrels, args.top_r), 4),
            "qrels_recall_sup": round(metrics.recall_at_k(
                rs.doc_ids, corpus.qrels, args.top_r), 4),
        })

    # loss trajectory: windowed means over 10 equal slices
    n = len(losses)
    w = max(1, n // 10)
    windows = [round(float(np.mean(losses[i:i + w])), 4)
               for i in range(0, n - w + 1, w)]
    improving = sum(b < a for a, b in zip(windows, windows[1:]))
    trajectory = {
        "n_steps": n,
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "window_means": windows,
        "frac_improving_windows": round(improving / max(
            1, len(windows) - 1), 4),
    }

    # (c) assembly bit-round-trips through the index checkpoint
    with tempfile.TemporaryDirectory() as tmp:
        path = ckpt.save_index(tmp, 0, sup)
        restored = ckpt.restore_index(path, sup)
    rt = hi.search(restored, qe, qt, kc=6, k2=8, top_r=args.top_r)
    rd = hi.search(sup, qe, qt, kc=6, k2=8, top_r=args.top_r)
    roundtrip = {
        "planes_bit_identical": _tree_equal(sup, restored),
        "search_bit_identical": bool(
            np.array_equal(np.asarray(rt.doc_ids), np.asarray(rd.doc_ids))
            and np.array_equal(np.asarray(rt.scores),
                               np.asarray(rd.scores))),
    }

    return {
        "codec": CODEC,
        "top_r": args.top_r,
        "oracle_width": ORACLE_WIDTH,
        "negative_pool": {"topic": int(topic.shape[1]),
                          "mined": int(mined.shape[1]),
                          "in_batch": cfg.n_inbatch},
        "refine_weight": cfg.refine_weight,
        "operating_points": points,
        "sup_wins": int(wins),
        "n_operating_points": len(points),
        "trajectory": trajectory,
        "roundtrip": roundtrip,
    }


# --------------------------------------------------------------------------
# stage: variants (subprocess, 2 emulated devices)
# --------------------------------------------------------------------------

def run_variants(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import checkpoint as ckpt
    from repro.core import hybrid_index as hi
    from repro.core import segments as seg
    from repro.launch import serve
    from repro.launch import train as tr

    corpus = _corpus(args)
    common = _common(args)
    b = 64
    qe, qt = (jnp.asarray(corpus.query_emb[:b]),
              jnp.asarray(corpus.query_tokens[:b]))
    kc, k2 = 6, 8

    # n_steps=0 reruns only the (deterministic) KMeans init — the
    # checkpoint written by the train stage supplies the trained values
    params0, enc_cfg, _, _ = tr.train_hi2_sup(corpus, _cfg(args, 0),
                                              log_every=0)
    params = ckpt.restore(args.params_ckpt, {"params": params0})["params"]
    sel = tr.SupSelectors(params=params, enc_cfg=enc_cfg)

    # all four layouts share one base: hi.build under the selector
    # bundle (argmax φ — the corpus-independent recipe compaction needs)
    sel_kwargs = sel.build_inputs(jnp.asarray(corpus.doc_emb),
                                  jnp.asarray(corpus.doc_tokens),
                                  corpus.vocab_size)
    base = hi.build(jax.random.key(0), jnp.asarray(corpus.doc_emb),
                    jnp.asarray(corpus.doc_tokens), corpus.vocab_size,
                    n_clusters=args.clusters, **sel_kwargs, **common)
    ref = hi.search(base, qe, qt, kc=kc, k2=k2, top_r=args.top_r)
    ref_ids = np.asarray(ref.doc_ids)

    def build_mut():
        return seg.MutableHybridIndex.create(
            jax.random.key(0), corpus.doc_emb, corpus.doc_tokens,
            corpus.vocab_size, delta_capacity=128, selectors=sel,
            **common)

    report = {}
    kw = dict(top_r=args.top_r, max_batch=b)
    report["plain"] = {"ids_identical": True}        # the reference
    sh = serve.make_server(base, serve.ServeConfig(
        kc=kc, k2=k2, n_shards=2, **kw))
    report["sharded"] = {"ids_identical": bool(np.array_equal(
        np.asarray(sh.query(corpus.query_emb[:b],
                            corpus.query_tokens[:b]).doc_ids), ref_ids))}
    mut = build_mut()
    report["mutable"] = {"ids_identical": bool(np.array_equal(
        np.asarray(mut.search(qe, qt, kc=kc, k2=k2,
                              top_r=args.top_r).doc_ids), ref_ids))}
    smut = serve.make_mutable_server(build_mut(), serve.ServeConfig(
        kc=kc, k2=k2, n_shards=2, mutable=True, delta_capacity=128, **kw))
    report["sharded_mutable"] = {"ids_identical": bool(np.array_equal(
        np.asarray(smut.query(corpus.query_emb[:b],
                              corpus.query_tokens[:b]).doc_ids), ref_ids))}

    # supervised mutable lifecycle: add → delete → compact, with the
    # compaction bit-identical to a from-scratch supervised build over
    # the survivors (the §10 contract, now under learned selectors)
    n0 = args.docs
    ids = mut.add_docs(corpus.query_emb[:16], corpus.query_tokens[:16])
    mut.delete_docs(ids[:4])
    mut.delete_docs(np.arange(8))
    comp = mut.compact()
    emb_s, tok_s = mut.surviving_corpus()
    scratch = seg.MutableHybridIndex.create(
        jax.random.key(0), emb_s, tok_s, corpus.vocab_size,
        delta_capacity=128, selectors=sel, **common)
    c_res = comp.search(qe, qt, kc=kc, k2=k2, top_r=args.top_r)
    s_res = scratch.search(qe, qt, kc=kc, k2=k2, top_r=args.top_r)
    report["mutable_lifecycle"] = {
        "n_live_after": int(comp.n_docs),
        "expected_live": int(n0 + 16 - 12),
        "compact_equals_scratch": bool(
            _tree_equal(comp.base, scratch.base)
            and np.array_equal(np.asarray(c_res.doc_ids),
                               np.asarray(s_res.doc_ids))
            and np.array_equal(np.asarray(c_res.scores),
                               np.asarray(s_res.scores))),
    }
    return report


# --------------------------------------------------------------------------
# orchestration + checks
# --------------------------------------------------------------------------

def _spawn(stage: str, argv: list, devices: int = 1) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = f"src:{env.get('PYTHONPATH', '')}".rstrip(":")
    if env.get("JAX_PLATFORMS") == "cpu":    # emulated devices on the CPU
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}").strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--stage", stage,
         *argv], capture_output=True, text=True, env=env)
    if r.returncode != 0:
        sys.exit(f"sup_distill --stage {stage} failed:\n"
                 f"{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout[r.stdout.index("{"):])


def _check(report: dict) -> list:
    fails = []
    # (a) matched cost, and sup must win somewhere
    for p in report["operating_points"]:
        if p["cost_sup"] != p["cost_unsup"]:
            fails.append(f"kc={p['kc']} k2={p['k2']}: costs not matched "
                         f"({p['cost_sup']} vs {p['cost_unsup']})")
    if report["sup_wins"] < 1:
        fails.append("sup recall < unsup at every matched operating "
                     "point — distillation buys nothing")
    # (b) loss trajectory monotone-ish
    t = report["trajectory"]
    if t["loss_last"] >= t["loss_first"]:
        fails.append(f"loss did not decrease ({t['loss_first']} -> "
                     f"{t['loss_last']})")
    if t["frac_improving_windows"] < MONOTONE_FRAC:
        fails.append(f"loss trajectory not monotone-ish: only "
                     f"{t['frac_improving_windows']} of windows improve "
                     f"(need >= {MONOTONE_FRAC})")
    # (c) checkpoint round-trip
    for k, v in report["roundtrip"].items():
        if not v:
            fails.append(f"index round-trip failed: {k}")
    # (d) four layouts + mutable lifecycle
    for layout in LAYOUTS:
        if not report["variants"][layout]["ids_identical"]:
            fails.append(f"{layout}: doc ids differ from the plain "
                         "supervised search")
    life = report["variants"]["mutable_lifecycle"]
    if life["n_live_after"] != life["expected_live"]:
        fails.append(f"mutable lifecycle lost docs: {life['n_live_after']}"
                     f" live, expected {life['expected_live']}")
    if not life["compact_equals_scratch"]:
        fails.append("supervised compact() != from-scratch supervised "
                     "build over the survivors")
    return fails


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus (CI scale)")
    ap.add_argument("--stage", default=None, choices=("train", "variants"),
                    help="run ONE stage in-process (internal: the "
                         "default orchestrates the subprocess stages)")
    ap.add_argument("--top-r", type=int, default=100)
    ap.add_argument("--steps", type=int, default=None,
                    help="override the training step count")
    ap.add_argument("--params-ckpt", default=None,
                    help="trained-params checkpoint: the directory "
                         "--stage train writes, the step --stage "
                         "variants reads")
    ap.add_argument("--out", default=None,
                    help="write BENCH_sup.json here")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless the §15 acceptance "
                         "contracts (a)-(d) hold")
    args = ap.parse_args(argv)
    _scale(args)

    if args.stage in ("train", "variants") and not args.params_ckpt:
        sys.exit(f"--stage {args.stage} needs --params-ckpt")
    if args.stage == "train":
        report = run_train_sweep(args, args.params_ckpt)
    elif args.stage == "variants":
        report = run_variants(args)
    else:
        sub = ["--top-r", str(args.top_r), "--steps", str(args.steps)]
        if args.smoke:
            sub.append("--smoke")
        with tempfile.TemporaryDirectory() as ckpt_dir:
            sweep = _spawn("train", sub + ["--params-ckpt", ckpt_dir])
            step_dir = os.path.join(
                ckpt_dir, sorted(os.listdir(ckpt_dir))[-1])
            report = {
                "bench": "sup_distill",
                "smoke": bool(args.smoke),
                "n_docs": args.docs,
                "n_queries": args.queries,
                **sweep,
                "variants": _spawn("variants",
                                   sub + ["--params-ckpt", step_dir],
                                   devices=2),
            }

    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.check and args.stage is None:
        failures = _check(report)
        if failures:
            sys.exit("; ".join(failures))


if __name__ == "__main__":
    main()
