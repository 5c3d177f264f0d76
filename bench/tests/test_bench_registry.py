"""The harness finds configurations, traffic mixes and metric readers by
name, picks up new files without an edit to an existing one, refuses a
device kind that the table of peaks lacks, and never runs without a
TPU."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.registry import BENCH_DIR, Registry, UnknownDevice  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_in_the_benchmark_has_its_file():
    reg = Registry(SPEC)
    for cell in SPEC["workloads"]:
        assert reg.workload(cell["name"]) is cell
        cfg = reg.config(cell["config"])
        assert cfg["name"] == cell["config"]
        traffic = reg.traffic(cell["traffic"])
        assert traffic["loop"] in ("closed", "open")
        assert reg.end_to_end(cell["name"])
        assert reg.per_layer(cell["name"])
    for m in SPEC["per_layer"]:
        assert callable(reg.reader(m["name"]))
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])
        assert {"score_err", "rank_gap"} <= set(cfg["limits"])


def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    reg = Registry(SPEC)
    for cell in SPEC["workloads"]:
        names = {m["name"] for m in reg.end_to_end(cell["name"])}
        assert "setup_s" in names and len(names) >= 2
        for m in reg.per_layer(cell["name"]):
            assert m["moves"] in names


def _snapshot(d: Path) -> dict:
    return {p.relative_to(d).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest() for p in sorted(d.rglob("*"))
        if p.is_file()}


def test_new_files_are_found_by_name_without_editing_any(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH_DIR / sub, bench / sub)
    shutil.copy(BENCH_DIR / "peaks.json", bench / "peaks.json")
    before = _snapshot(bench)

    cfg = json.loads((bench / "configs" / "hi2-msmarco-opq.json")
                     .read_text())
    cfg["name"] = "new-config"
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(
        {"loop": "open", "arrivals": "poisson", "rate_qps": 5.0,
         "pool": 64}))
    (bench / "metrics" / "new_metric.online.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.run['x']\n")
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "new.cell", "config": "new-config",
                              "traffic": "new-mix", "chips": 1})
    spec["per_layer"].append({"name": "new_metric.online", "unit": "x",
                              "better": "lower", "source": "host_clock",
                              "layer": "runtime", "moves": "setup_s",
                              "workloads": ["new.cell"]})

    reg = Registry(spec, bench)
    assert reg.config("new-config")["name"] == "new-config"
    assert reg.traffic("new-mix")["rate_qps"] == 5.0
    ctx = SimpleNamespace(run={"x": 21.0, "build_s": 1.0, "compile_s": 2.0})
    layer = reg.read_layer("new.cell", ctx)
    assert layer["new_metric.online"] == {"value": 42.0, "unit": "x"}
    assert layer["build_s"]["value"] == 1.0     # metrics without a list
    after = _snapshot(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_the_new_traffic_mix_runs_through_the_one_generator():
    import numpy as np

    from bench import loops

    plain = loops.arrivals({"rate_qps": 10.0}, 20.0)
    assert len(plain) == 200
    # the schedule is the traffic file's: the same for every seed, and
    # its gaps are the exponential distribution's quantiles at the rate
    assert np.array_equal(plain, loops.arrivals({"rate_qps": 10.0}, 20.0))
    assert np.diff(plain, prepend=0.0).mean() == pytest.approx(0.1,
                                                               rel=0.02)


def test_an_unknown_device_kind_is_refused():
    reg = Registry(SPEC)
    assert reg.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(UnknownDevice):
        reg.peaks("TPU v9 imaginary")


def _run_bench(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sq8r.batch",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_without_a_tpu_the_benchmark_exits_nonzero_and_prints_nothing():
    out = _run_bench(ROOT)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_with_only_the_benchmark_files_it_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _run_bench(tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
