"""The share of live candidate slots, on a hand-made run."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.registry import Registry  # noqa: E402

CFG = json.loads((ROOT / "bench" / "configs" /
                  "hi2-msmarco-sq8r.json").read_text())


def _ctx(attempted, failed, live):
    return SimpleNamespace(
        trace=None, cfg=CFG, peaks={}, workload="sq8r.batch",
        run={"stats": {"attempted": attempted, "failed": failed},
             "live_candidates": live})


def test_live_share_of_two_steps_of_64_queries():
    read = Registry.load(ROOT).reader("live_share.batch")
    slots = 30 * 1024 + 32 * 1024            # K^C, K2^T lists at 1024
    assert slots == 63_488
    live = 128 * 5_270                       # 2 steps × 64 queries
    assert read(_ctx(128, 0, live)) == pytest.approx(
        100.0 * 5_270 / 63_488, rel=1e-12)
    # a failed query answers nothing and counts no slots
    assert read(_ctx(130, 2, live)) == pytest.approx(
        100.0 * 5_270 / 63_488, rel=1e-12)


def test_live_share_is_silent_when_no_query_was_answered():
    read = Registry.load(ROOT).reader("live_share.batch")
    assert read(_ctx(0, 0, 0)) is None
    assert read(_ctx(64, 64, 0)) is None


def test_live_share_is_a_scoring_metric_of_the_batch_cell():
    reg = Registry.load(ROOT)
    metric = {m["name"]: m for m in reg.per_layer("sq8r.batch")}[
        "live_share.batch"]
    assert (metric["layer"], metric["moves"], metric["unit"]) == (
        "scoring", "qps", "%")
