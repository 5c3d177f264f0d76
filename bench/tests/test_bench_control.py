"""The check of ``correct`` fails what it must: the control (the plain
reference in bfloat16 put in the program's place), and a whole run of the
harness with the timed path broken underneath (an answer altered where
it is produced; half of each batch left out).  At the tiny fixture sizes
on the CPU; the chip readings are in PERF.md."""
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import calibrate, run  # noqa: E402
from bench.registry import Registry  # noqa: E402
from bench.session import Session  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
SPEC = {
    "workloads": [
        {"name": "tiny.batch", "config": "tiny-opq", "traffic": "batch8",
         "chips": 1},
        {"name": "tiny.online", "config": "tiny-opq",
         "traffic": "poisson-tiny", "chips": 1},
        {"name": "tiny-sq8r.batch", "config": "tiny-sq8r",
         "traffic": "batch8", "chips": 1}],
    "end_to_end": json.loads((ROOT / "BENCHMARK.json").read_text())[
        "end_to_end"],
    "per_layer": []}
PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny-sq8r.batch"])
def test_the_control_fails_and_the_program_passes(workload):
    reg = Registry(SPEC, FIXTURES)
    sess = Session(reg, workload, 2 ** 31 + 21)
    got = calibrate.readings(sess, 32)
    limits = sess.cfg["limits"]
    assert all(got["program"][k] <= limits[k] for k in limits), got
    assert any(got["control"][k] > limits[k] for k in limits), got


def _altered(search, n_docs):
    def broken(*a, **k):
        res = search(*a, **k)
        ids = res.doc_ids.at[:, 5].set((res.doc_ids[:, 5] + 1) % n_docs)
        return res._replace(doc_ids=ids)
    return broken


def _half(search, n_docs):
    def broken(*a, **k):
        res = search(*a, **k)
        half = res.doc_ids.shape[0] // 2
        return res._replace(doc_ids=res.doc_ids.at[half:].set(-1),
                            scores=res.scores.at[half:].set(0.0))
    return broken


@pytest.mark.parametrize("fault,workload", [
    (None, "tiny.batch"), (_altered, "tiny.batch"),
    (_half, "tiny.batch"), (_altered, "tiny.online")])
def test_a_run_with_the_timed_path_broken_is_not_correct(
        monkeypatch, fault, workload):
    from repro.core import hybrid_index as hi

    reg = Registry(SPEC, FIXTURES)
    if fault is not None:
        n_docs = reg.config(reg.workload(workload)["config"])["n_docs"]
        monkeypatch.setattr(hi, "search", fault(hi.search, n_docs))
    result, checks, _ = run.run_cell(reg, workload, 2 ** 31 + 7, 0.5,
                                     False, time.time(), PEAKS)
    assert result["correct"] is (fault is None), checks
    assert list(result)[-1] == "checks"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
