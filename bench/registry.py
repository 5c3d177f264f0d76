"""Finds what ``BENCHMARK.json`` names, by name, in files of their own.

    bench/configs/<config>.json   the index and serve settings
    bench/traffic/<mix>.json      the loop kind and its parameters
    bench/metrics/<metric>.py     one reader per per-layer metric,
                                  ``read(ctx) -> float | None``
    bench/peaks.json              the chips' peaks, by ``device_kind``

A configuration, a traffic mix or a per-layer metric is added as a new
file and an entry in ``BENCHMARK.json``; nothing here lists them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent


class UnknownDevice(LookupError):
    """A device kind that the table of peaks does not hold."""


class Registry:
    def __init__(self, spec: dict, bench_dir: Path = BENCH_DIR):
        self.spec = spec
        self.dir = Path(bench_dir)

    @classmethod
    def load(cls, root: Path, bench_dir: Path = BENCH_DIR) -> "Registry":
        return cls(json.loads((Path(root) / "BENCHMARK.json").read_text()),
                   bench_dir)

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.spec[key])
        raise KeyError(f"no {key[:-1]} named {name!r} (known: {known})")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        return json.loads((self.dir / "configs" / f"{name}.json")
                          .read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json")
                          .read_text())

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        try:
            return table["devices"][device_kind]
        except KeyError:
            raise UnknownDevice(
                f"device kind {device_kind!r} is not in peaks.json "
                f"(known: {sorted(table['devices'])})") from None

    def reader(self, metric: str) -> Callable:
        path = self.dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def end_to_end(self, workload: str) -> list:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list:
        """The per-layer metrics this cell reports: those that list it,
        and those without a list whose end-to-end metric it reports."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.spec["per_layer"]:
            cells = m.get("workloads")
            if (workload in cells) if cells is not None else (
                    m["moves"] in e2e):
                out.append(m)
        return out

    def read_layer(self, workload: str, ctx) -> dict:
        """Every per-layer metric of the cell that its reader finds."""
        out = {}
        for m in self.per_layer(workload):
            value: Optional[float] = self.reader(m["name"])(ctx)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
