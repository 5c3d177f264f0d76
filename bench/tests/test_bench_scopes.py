"""Device time by stage (:mod:`bench.scopes`): the stage map of a
compiled program's text, the stage readers on hand-made numbers, the
stage map lowered from the served shapes against the program that
serves, and the reduction of a small trace recorded on one TPU v5e with
the stage scopes and the program's host spans
(``record_stage_trace.py``)."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import scopes, trace_reduce  # noqa: E402
from bench.registry import Registry  # noqa: E402

TRACE = HERE / "fixtures" / "trace"
RECORDED = TRACE / "tiny_sq8r.xplane.pb"
STAGES = TRACE / "tiny_sq8r.stages.json"
OLD = TRACE / "tiny_opq.xplane.pb"
READERS = ("dispatch", "gather", "dedup", "score", "topk", "refine",
           "unscoped")

TEXT = """HloModule jit_search, entry_computation_layout={(f32[8]{0})->f32[8]}

FileNames
1 "stages.py"

%fused_computation.5 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %neg.1 = s32[8]{0} negate(%param_0), metadata={op_name="jit(search)/hi2.dedup/jit(dedup_mask)/neg"}
}

ENTRY %main.3 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="query_embeddings"}
  %fusion.5 = s32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(search)/hi2.dedup/jit(dedup_mask)/jit(take_along_axis)/gather" stack_frame_id=3}
  %copy-start.2 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%fusion.5)
  sq8_dot_fused.1 = f32[8]{0} custom-call(%x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(search)/hi2.score/jit(sq8_dot_fused)/sq8_dot_fused/pallas_call"}
  ROOT %odd.1 = f32[8]{0} add(%x.1, %x.1), metadata={op_name="jit(search)/hi2.score/hi2.topk/add"}
}
"""


def test_stage_map_of_a_compiled_text():
    smap = scopes.stage_map(TEXT)
    assert smap["fusion.5"] == "dedup"
    assert smap["sq8_dot_fused.1"] == "score"
    assert smap["neg.1"] == "dedup"
    # no op_name, a parameter's op_name, two stages at once: no stage
    assert smap["copy-start.2"] == scopes.UNSCOPED
    assert smap["x.1"] == scopes.UNSCOPED
    assert smap["odd.1"] == scopes.UNSCOPED
    assert "HloModule" not in smap and "fused_computation.5" not in smap


def _ctx(ops, count=2, trace=True):
    red = {"ops": ops, "op_text": {},
           "modules": {"jit_search(1)": {"count": count, "seconds": 1.0}}}
    return SimpleNamespace(trace=red if trace else None, cfg={"x": 1},
                           run={}, peaks={}, workload="w")


def test_stage_ms_per_execution_and_silence_without_scopes(monkeypatch):
    smap = scopes.stage_map(TEXT)
    monkeypatch.setattr(scopes, "cell_stage_map", lambda cfg: smap)
    ctx = _ctx({"fusion.5": 0.010, "sq8_dot_fused.1": 0.900,
                "copy-start.2": 0.004, "other_program_op.1": 5.0})
    assert scopes.stage_ms(ctx, "dedup") == pytest.approx(5.0)
    assert scopes.stage_ms(ctx, "score") == pytest.approx(450.0)
    assert scopes.stage_ms(ctx, scopes.UNSCOPED) == pytest.approx(2.0)
    assert scopes.stage_ms(ctx, "refine") == 0.0
    assert scopes.stage_ms(_ctx({}, trace=False), "score") is None
    assert scopes.stage_ms(_ctx({"fusion.5": 1.0}, count=0), "dedup") is None
    # a program traced without the scopes: every reader is silent
    monkeypatch.setattr(scopes, "cell_stage_map",
                        lambda cfg: {k: scopes.UNSCOPED for k in smap})
    reg = Registry.load(ROOT)
    for stage in READERS:
        metric = f"{stage}_ms.batch"
        assert reg.reader(metric)(ctx) is None, metric


def test_every_stage_reader_is_in_the_benchmark():
    reg = Registry.load(ROOT)
    names = {m["name"] for m in reg.per_layer("sq8r.batch")}
    assert {f"{s}_ms.batch" for s in READERS} <= names


def test_stage_map_from_served_shapes_matches_the_served_program():
    """The stage map the readers compile from the configuration alone
    names the same instructions, with the same stages, as the program
    ``Server.query`` runs (CPU, tiny sq8r fixture)."""
    import jax.numpy as jnp

    from bench.session import Session
    from repro.core import hybrid_index as hi

    spec = {"workloads": [{"name": "tiny", "config": "tiny-sq8r",
                           "traffic": "batch8", "chips": 1}]}
    sess = Session(Registry(spec, HERE / "fixtures"), "tiny", 3)
    cfg = sess.cfg
    b = cfg["max_batch"]
    qe = jnp.asarray(np.pad(sess.qe[:3], ((0, b - 3), (0, 0))))
    qt = jnp.asarray(np.pad(sess.qt[:3], ((0, b - 3), (0, 0)),
                            constant_values=-1))
    served = hi.search.lower(
        sess.index, qe, qt, kc=cfg["kc"], k2=cfg["k2"], top_r=cfg["top_r"],
        use_kernel=cfg["use_kernel"], filter=None,
        fusion=None).compile().as_text()
    want = scopes.stage_map(served)
    got = scopes.cell_stage_map(cfg)
    assert got == want
    assert {"dispatch", "gather", "dedup", "score", "topk",
            "refine"} <= set(got.values())


def _recorded():
    events = trace_reduce.load(RECORDED)
    return events, trace_reduce.reduce(events)


def test_recorded_trace_splits_the_search_program_by_stage():
    _, red = _recorded()
    smap = json.loads(STAGES.read_text())
    by_stage = scopes.stage_seconds(red, smap)
    n, step = trace_reduce.module_stats(red, scopes.MODULE)
    assert n >= 1 and step > 0
    # the stages and the unscoped rest partition the program's op time
    program_ops = sum(sec for name, sec in red["ops"].items()
                      if name in smap)
    assert sum(by_stage.values()) == pytest.approx(program_ops, rel=1e-12)
    assert set(red["ops"]) <= set(smap)
    for stage in ("dispatch", "gather", "dedup", "score", "topk",
                  "refine"):
        assert by_stage.get(stage, 0.0) > 0, stage
    # the fused scoring kernel is in the score stage
    kernel = trace_reduce.op_seconds(red, trace_reduce.kernel_match(
        "sq8_dot"))
    assert kernel > 0
    assert all(smap[name] == "score" for name in red["ops"]
               if trace_reduce.kernel_match("sq8_dot")(name, ""))
    assert kernel <= by_stage["score"]
    # op time counts overlapping async copies in each op: at least the
    # union of busy time of the program's executions
    assert sum(by_stage.values()) >= 0.99 * red["busy_s"]


def test_recorded_trace_holds_the_program_host_spans():
    """``Server.query``'s spans are in the trace, under the harness's
    ``bench.call``, each call carrying its id."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(RECORDED))
    host = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("hi2.", "bench.call")):
                    host.setdefault(ev.name, []).append(ev)
    names = set(host)
    assert {"hi2.query", "hi2.query.pad", "hi2.query.search",
            "hi2.query.split", "bench.call"} <= names
    ids = [dict(ev.stats).get("id") for ev in host["hi2.query"]]
    assert all(isinstance(i, int) for i in ids)
    assert ids == sorted(ids) and len(set(ids)) == len(ids)
    calls = host["bench.call"]
    for ev in host["hi2.query"]:
        assert any(c.start_ns <= ev.start_ns and ev.end_ns <= c.end_ns
                   for c in calls)


def test_older_fixture_reduces_as_before():
    """The reduction of the first recorded trace, unchanged."""
    red = trace_reduce.reduce(trace_reduce.load(OLD))
    assert red["window_s"] == pytest.approx(0.051616785, rel=1e-12)
    assert red["busy_s"] == pytest.approx(0.005854375, rel=1e-12)
    assert red["idle_share"] == pytest.approx(0.8865800146212128,
                                              rel=1e-12)
    assert len(red["ops"]) == 81
    assert sum(red["ops"].values()) == pytest.approx(0.006940814,
                                                     rel=1e-9)
    assert red["modules"] == {"jit_search(14550282403125790443)": {
        "count": 11, "seconds": pytest.approx(0.005863268, rel=1e-9)}}
