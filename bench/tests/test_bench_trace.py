"""The trace reduction on a hand-made trace with known numbers and on a
small trace recorded on one TPU v5e (``record_trace.py``)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cost, trace_reduce  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "fixtures" / "trace" / \
    "tiny_opq.xplane.pb"

# host window 0–100 µs; device ops (µs): sort 10–30, fusion 25–40 (overlaps
# the sort), the kernel 60–70, a second kernel call 90–110 (runs past the
# window's close); one program execution 10–70
TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000
             stats { metadata_id: 9 str_value: "pallas_call" } }
    events { metadata_id: 3 offset_ps: 90000000 duration_ps: 20000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "sort.7" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.12" } }
  event_metadata { key: 3 value { id: 3 name: "pq_adc_fused.1" } }
  event_metadata { key: 4 value { id: 4 name: "jit_search(123)" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 5 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 8000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 20000000 }
    events { metadata_id: 4 offset_ps: 70000000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.call" } }
  event_metadata { key: 3 value { id: 3 name: "bench.read" } }
  event_metadata { key: 4 value { id: 4 name: "unrelated" } }
}
"""


def _reduced_text():
    from jax.profiler import ProfileData
    return trace_reduce.reduce(list(trace_reduce.events_of(
        ProfileData.from_text_proto(TEXT))))


def test_busy_union_and_idle_share_of_a_known_trace():
    red = _reduced_text()
    # union: 10–40, 60–70, 90–100 (clipped to the window) = 50 µs
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(50e-6)
    assert red["idle_share"] == pytest.approx(0.5)
    assert red["n_devices"] == 1


def test_time_by_op_category_module_and_kernel():
    red = _reduced_text()
    assert red["ops"]["sort.7"] == pytest.approx(20e-6)
    assert red["ops"]["pq_adc_fused.1"] == pytest.approx(30e-6)
    assert red["categories"]["sort"] == pytest.approx(20e-6)
    assert red["categories"]["fusion"] == pytest.approx(15e-6)
    assert trace_reduce.module_stats(red, "jit_search") == (
        1, pytest.approx(60e-6))
    kernel = trace_reduce.op_seconds(red, trace_reduce.kernel_match("pq_adc"))
    assert kernel == pytest.approx(30e-6)
    assert trace_reduce.op_seconds(
        red, trace_reduce.kernel_match("sq8_dot")) == 0.0


def test_gaps_are_labelled_by_the_innermost_harness_span():
    red = _reduced_text()
    # gaps 0–10 (bench.call up to 8, but its middle at 5 is inside it),
    # 40–60 (bench.read), 70–90 (no bench span: "unrelated" is not ours)
    assert red["gaps_by_span"] == {
        "bench.call": pytest.approx(10e-6),
        "bench.read": pytest.approx(20e-6),
        "idle": pytest.approx(20e-6)}
    top = trace_reduce.breakdown(red)
    assert top["device_ops"][0][0] == "pq_adc_fused.1"
    assert [g[0] for g in top["idle_gaps"]][0] in ("bench.read", "idle")


def test_a_tpu_instruction_is_named_by_its_hlo_name():
    full = ("%sort.32 = (f32[64,63488]{1,0:T(8,128)S(1)}, s32[64,63488]) "
            "sort(f32[64,63488] %fusion.7, s32[64,63488] %iota.28)")
    assert trace_reduce.op_name(full) == "sort.32"
    assert trace_reduce.category(full) == "sort"
    assert trace_reduce.category("pq_adc_fused.1") == "pq_adc_fused"


def test_a_trace_without_device_operations_reads_no_idle_share():
    red = trace_reduce.reduce([trace_reduce.Event(
        "/host:CPU", "python", "bench.window", 0.0, 1e6, "")])
    assert red["idle_share"] is None and red["busy_s"] == 0.0


def _sweep_busy(events, w0, w1):
    """Busy time by a sweep over the sorted start and end points, counting
    the operations in flight (another algorithm than the reduction's)."""
    points = []
    for e in events:
        if e.plane.startswith("/device:") and trace_reduce.OPS_LINE in e.line:
            lo, hi = max(e.start_ns, w0), min(e.end_ns, w1)
            if hi > lo:
                points += [(lo, 1), (hi, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(points, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_chip_trace():
    events = trace_reduce.load(RECORDED)
    red = trace_reduce.reduce(events)
    assert red["n_devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    w0 = next(e.start_ns for e in events
              if e.name == trace_reduce.WINDOW_SPAN)
    w1 = w0 + red["window_s"] * 1e9
    assert red["busy_s"] == pytest.approx(
        _sweep_busy(events, w0, w1) / 1e9, rel=1e-9)
    n, seconds = trace_reduce.module_stats(red, "jit_search")
    assert n >= 1 and seconds > 0
    kernel = trace_reduce.op_seconds(red, trace_reduce.kernel_match("pq_adc"))
    assert 0 < kernel < seconds
    assert sum(red["gaps_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert set(red["gaps_by_span"]) <= {"bench.call", "bench.read", "idle"}


def test_roofline_counts_follow_the_live_candidates():
    peaks = {"bf16_flops": 197e12, "int8_ops": 393e12,
             "hbm_bytes_per_s": 819e9}
    cfg = {"pq_m": 96, "hidden": 768}
    least, bound = cost.least_seconds("pq_adc", 1000, cfg, peaks)
    assert bound == "hbm" and least == pytest.approx(1000 * 100 / 819e9)
    share = cost.roofline_share("pq_adc", 1000, 2 * least, cfg, peaks)
    assert share == pytest.approx(50.0)
    assert cost.roofline_share("pq_adc", 1000, 0.0, cfg, peaks) is None
