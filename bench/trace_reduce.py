"""Reduces a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

    busy      the union of the intervals in which an operation ran on a
              device, inside the traced window, averaged over the
              devices that ran any; idle share = 1 - busy / window
    ops       device seconds by HLO operation name (``fusion.12``; on a
              TPU the trace gives the whole instruction, whose name is
              kept), and by category (the name without its number:
              ``fusion``, ``sort``, ``pq_adc_fused``)
    modules   executions and device seconds of each XLA program
    gaps      the idle time inside the window, labelled by the
              innermost host span of the harness (``bench.*``) open at
              the middle of each gap; ``idle`` where none is

The window is the host span ``bench.window`` when the trace has one,
else the extent of the device operations.  Device operations are the
events on a device plane's ``XLA Ops`` lines; programs those on its
``XLA Modules`` lines.  A trace is read with ``jax.profiler`` alone.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Iterable, NamedTuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_NUMBER = re.compile(r"\.\d+$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    text: str          # the event's string statistics, joined

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> list:
    """The events of one trace file that the reduction uses."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return list(events_of(pd))


def events_of(pd) -> Iterable[Event]:
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            device_line = OPS_LINE in line.name or MODULES_LINE in line.name
            for ev in line.events:
                keep = (device_line if device
                        else ev.name.startswith(SPAN_PREFIX))
                if not keep:
                    continue
                text = " ".join(str(v) for _, v in ev.stats
                                if isinstance(v, str))
                yield Event(plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns), text)


def op_name(name: str) -> str:
    """An operation's HLO name: the trace of a TPU names an operation by
    its whole instruction (``%sort.32 = (f32[64,63488]...) sort(...)``)."""
    if name.startswith("%"):
        return name[1:].split(" ", 1)[0]
    return name


def category(name: str) -> str:
    return _NUMBER.sub("", op_name(name))


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def reduce(events: list) -> dict:
    spans = [e for e in events if not e.plane.startswith("/device:")]
    ops = [e for e in events if e.plane.startswith("/device:")
           and OPS_LINE in e.line]
    modules = [e for e in events if e.plane.startswith("/device:")
               and MODULES_LINE in e.line]
    window = [e for e in spans if e.name == WINDOW_SPAN]
    if window:
        w0, w1 = window[0].start_ns, window[0].end_ns
    elif ops:
        w0 = min(e.start_ns for e in ops)
        w1 = max(e.end_ns for e in ops)
    else:
        w0 = w1 = 0.0
    inside = [e for e in ops if e.end_ns > w0 and e.start_ns < w1]

    per_device: dict = {}
    for e in inside:
        per_device.setdefault(e.plane, []).append(
            (max(e.start_ns, w0), min(e.end_ns, w1)))
    busy_by_device = {}
    union_by_device = {}
    for plane, iv in per_device.items():
        u = _union(iv)
        union_by_device[plane] = u
        busy_by_device[plane] = sum(hi - lo for lo, hi in u)
    n_dev = len(busy_by_device)
    busy_ns = sum(busy_by_device.values()) / n_dev if n_dev else 0.0
    window_ns = w1 - w0

    by_op: dict = {}
    by_cat: dict = {}
    op_text: dict = {}
    for e in inside:
        name = op_name(e.name)
        by_op[name] = by_op.get(name, 0.0) + e.dur_ns / 1e9
        cat = category(name)
        by_cat[cat] = by_cat.get(cat, 0.0) + e.dur_ns / 1e9
        op_text.setdefault(name, e.text)
    by_module: dict = {}
    for e in modules:
        if e.end_ns > w0 and e.start_ns < w1:
            rec = by_module.setdefault(e.name, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += e.dur_ns / 1e9

    gaps = []
    if union_by_device:
        # the gaps of the first device that ran anything
        u = union_by_device[sorted(union_by_device)[0]]
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                gaps.append((_label(spans, (lo + hi) / 2), (hi - lo) / 1e9))
    elif window_ns > 0:
        gaps.append((_label(spans, (w0 + w1) / 2), window_ns / 1e9))
    gaps_by_span: dict = {}
    for label, sec in gaps:
        gaps_by_span[label] = gaps_by_span.get(label, 0.0) + sec

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": (1.0 - busy_ns / window_ns)
        if window_ns > 0 and n_dev else None,
        "n_devices": n_dev,
        "ops": by_op,
        "categories": by_cat,
        "op_text": op_text,
        "modules": by_module,
        "gaps_by_span": gaps_by_span,
        "longest_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
    }


def _label(spans: list, t: float) -> str:
    """The innermost harness span open at ``t`` (the window aside)."""
    best = None
    for s in spans:
        if s.name == WINDOW_SPAN or not (s.start_ns <= t <= s.end_ns):
            continue
        if best is None or s.start_ns > best.start_ns:
            best = s
    return best.name if best is not None else "idle"


def op_seconds(red: dict, match) -> float:
    """Device seconds of the operations whose name or statistics
    ``match`` (a callable on (name, text))."""
    return sum(sec for name, sec in red["ops"].items()
               if match(name, red["op_text"].get(name, "")))


def module_stats(red: dict, prefix: str) -> tuple:
    """(executions, device seconds) of the programs named ``prefix*``."""
    n = s = 0
    for name, rec in red["modules"].items():
        if name.startswith(prefix):
            n += rec["count"]
            s += rec["seconds"]
    return n, s


def breakdown(red: dict) -> dict:
    """The ten device operations that took most time, and idle time by
    the host span open during it."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(red["gaps_by_span"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


#: the names by which a scoring kernel shows in the trace: the Pallas
#: kernel functions of the program's fused scorers
KERNEL_NAMES = {"pq_adc": ("_adc_fused_kernel", "pq_adc"),
                "sq8_dot": ("_sq8_fused_kernel", "sq8_dot")}


def kernel_match(kernel: str):
    tokens = KERNEL_NAMES[kernel]
    return lambda name, text: any(t in name or t in text for t in tokens)
