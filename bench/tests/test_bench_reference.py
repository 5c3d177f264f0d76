"""The plain reference agrees with the program's search on both codecs
at a tiny size; the exact oracle is exact; the work counts of the
roofline metrics and the slot count follow the configurations' shapes."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, cost, oracle  # noqa: E402
from bench.reference import Reference  # noqa: E402
from bench.registry import Registry  # noqa: E402
from bench.session import Session  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def _reference(sess: Session) -> Reference:
    trained = sess.trained()
    return Reference(trained, sess.planes(trained), sess.cfg, sess.rows())


def _session(config: str, seed: int) -> Session:
    spec = {"workloads": [{"name": "t", "config": config,
                           "traffic": "batch8", "chips": 1}]}
    return Session(Registry(spec, FIXTURES), "t", seed)


@pytest.mark.parametrize("config,use_kernel", [
    ("tiny-opq", False), ("tiny-opq", True),
    ("tiny-sq8r", False), ("tiny-sq8r", True)])
def test_reference_agrees_with_the_program(config, use_kernel):
    from repro.core import hybrid_index as hi

    sess = _session(config, 2 ** 31 + 11)
    cfg = sess.cfg
    res = hi.search(sess.index, sess.qe[:32], sess.qt[:32], kc=cfg["kc"],
                    k2=cfg["k2"], top_r=cfg["top_r"], use_kernel=use_kernel)
    ids, scores = np.asarray(res.doc_ids), np.asarray(res.scores)
    ref = _reference(sess)
    for q in range(32):
        err, gap = check.compare(ids[q], scores[q],
                                 ref.options(sess.qe[q], sess.qt[q]))
        assert err < 1e-5, (q, err)
        assert gap < 1e-6, (q, gap)
    assert check.contract_violations(list(ids), list(scores), cfg["top_r"],
                                     cfg["n_docs"]) == 0


def test_an_answer_from_outside_the_candidates_is_caught():
    from repro.core import hybrid_index as hi

    sess = _session("tiny-opq", 5)
    cfg = sess.cfg
    res = hi.search(sess.index, sess.qe[:4], sess.qt[:4], kc=cfg["kc"],
                    k2=cfg["k2"], top_r=cfg["top_r"])
    ids, scores = np.asarray(res.doc_ids), np.asarray(res.scores)
    ref = _reference(sess)
    opts = ref.options(sess.qe[0], sess.qt[0])
    cand = set().union(*(o.ids.tolist() for o in opts))
    outside = next(d for d in range(cfg["n_docs"]) if d not in cand)
    bad = ids[0].copy()
    bad[3] = outside
    assert check.compare(bad, scores[0], opts) == (np.inf, np.inf)


def _faulty(index, plane):
    """The index with one plane the build made altered."""
    import dataclasses

    if plane.endswith("codes"):
        codes = index.doc_planes["codes"]
        # one bit of every document's first code, or whole rows swapped
        bad = (codes.at[:, 0].set(codes[:, 0] ^ 1) if plane == "codes"
               else codes[::-1])
        return dataclasses.replace(
            index, doc_planes={**index.doc_planes, "codes": bad})
    lists = getattr(index, f"{plane}_lists")
    rolled = lists._replace(entries=lists.entries[::-1])
    return dataclasses.replace(index, **{f"{plane}_lists": rolled})


@pytest.mark.parametrize("config,plane", [
    ("tiny-opq", "codes"), ("tiny-sq8r", "row codes"),
    ("tiny-opq", "cluster")])
def test_a_fault_in_a_plane_the_build_made_is_caught(config, plane):
    """The reference builds its own lists and codes: an index whose
    codes or lists are wrong does not agree with it.  (Under refine a
    code moves only the frontier, so there a whole row is swapped.)"""
    from repro.core import hybrid_index as hi

    sess = _session(config, 2 ** 31 + 13)
    cfg = sess.cfg
    res = hi.search(_faulty(sess.index, plane), sess.qe[:16], sess.qt[:16],
                    kc=cfg["kc"], k2=cfg["k2"], top_r=cfg["top_r"])
    ids, scores = np.asarray(res.doc_ids), np.asarray(res.scores)
    ref = _reference(sess)
    worst = max(max(check.compare(ids[q], scores[q],
                                  ref.options(sess.qe[q], sess.qt[q])))
                for q in range(16))
    assert worst > 10 * cfg["limits"]["score_err"], worst


def test_the_reference_builds_the_programs_lists_and_codes():
    """Each list the program built holds every document the reference
    has surely in it and only ones it may have; so do the codes.  A
    term list of another term does not."""
    sess = _session("tiny-opq", 2 ** 31 + 17)
    index = sess.index
    planes = sess.planes(sess.trained())
    for lists, built in ((planes.clusters, index.cluster_lists),
                         (planes.terms, index.term_lists)):
        entries = np.asarray(built.entries)
        for v in range(entries.shape[0]):
            got = set(entries[v][entries[v] >= 0].tolist())
            sure, maybe = lists.members([v])
            assert set(sure.tolist()) <= got <= set(sure.tolist()) | set(
                maybe.tolist()), v
    entries = np.asarray(index.term_lists.entries)
    busy = np.flatnonzero((entries >= 0).sum(axis=1) > 4)
    sure, _ = planes.terms.members([busy[0]])
    assert set(sure.tolist()) != set(entries[busy[1]][
        entries[busy[1]] >= 0].tolist())
    codes = np.asarray(index.doc_planes["codes"])
    alt = set(zip(planes.codes.alt_doc.tolist(),
                  planes.codes.alt_pos.tolist()))
    d, j = np.nonzero(codes != planes.codes.best)
    assert all((a, b) in alt for a, b in zip(d.tolist(), j.tolist()))


def test_the_capacity_cut_keeps_ties_by_id_and_leaves_near_ties_open():
    from bench import derive

    # scores 5, 4, 4, 4 - 1e-9, 3; capacity 2
    s = np.asarray([5.0, 4.0, 4.0, 4.0 - 1e-9, 3.0])
    status = derive._cut(s, np.zeros(5, bool), 2)
    # the second 4.0 has 5 and the first 4.0 ahead of it in any order
    assert status.tolist() == [derive.SURE, derive.MAYBE, derive.OUT,
                               derive.MAYBE, derive.OUT]
    s = np.asarray([5.0, 4.0, 4.0, 4.0, 3.0])
    status = derive._cut(s, np.zeros(5, bool), 2)
    assert status.tolist() == [derive.SURE, derive.SURE, derive.OUT,
                               derive.OUT, derive.OUT]
    # a maybe member ahead leaves the next one open
    status = derive._cut(s, np.asarray([True, False, False, False, False]),
                         2)
    assert status.tolist() == [derive.MAYBE, derive.SURE, derive.MAYBE,
                               derive.OUT, derive.OUT]


def test_the_oracle_is_exact():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    docs = rng.normal(size=(4096, 64)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    q = docs[rng.choice(4096, 70)] + 0.3 * rng.normal(size=(70, 64))
    q = (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)
    got, rescans = oracle.exact_topk(q, jnp.asarray(docs), 10)
    want = np.argsort(-(q.astype(np.float64) @ docs.T.astype(np.float64)),
                      axis=1, kind="stable")[:, :10]
    assert (np.sort(got, 1) == np.sort(want, 1)).all()
    assert rescans == 0


def test_work_counts_at_the_configurations_shapes():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    reg = Registry(spec)
    opq = reg.config("hi2-msmarco-opq")
    sq8 = reg.config("hi2-msmarco-sq8r")
    for cfg in (opq, sq8):
        assert cost.candidate_budget(
            cfg["kc"], cfg["k2"], cfg["cluster_capacity"],
            cfg["term_capacity"]) == 30 * 1024 + 32 * 1024 == 63_488
    live = 5_243 * 64
    assert cost.scoring_work("pq_adc", live, opq) == (
        live * (96 + 4), live * 96, "bf16_flops")
    assert cost.scoring_work("sq8_dot", live, sq8) == (
        live * (768 + 4), live * 2 * 768, "int8_ops")
    peaks = reg.peaks("TPU v5 lite")
    t, bound = cost.least_seconds("pq_adc", live, opq, peaks)
    assert bound == "hbm" and t == pytest.approx(live * 100 / 819e9)
    t, bound = cost.least_seconds("sq8_dot", live, sq8, peaks)
    assert bound == "hbm" and t == pytest.approx(live * 772 / 819e9)


def test_the_slot_count_matches_the_programs_cost_model():
    from repro.core.exec import cost as program_cost

    assert cost.candidate_budget(30, 32, 1024, 1024) == \
        program_cost.candidate_budget(30, 32, [(1024, 1024)])
