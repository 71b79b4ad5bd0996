"""The harness finds every part of a cell by name, and refuses to run
without the chip."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from bench import harness, spec  # noqa: E402
from tiny import make_root  # noqa: E402


def test_every_cell_of_the_benchmark_resolves_by_name():
    bm = spec.benchmark()
    for w in bm["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("open", "closed")
        for trace in (False, True):
            for m in spec.metrics_of(bm, w["name"], trace):
                assert hasattr(spec.reader(m["name"]), "read")


def test_a_new_config_mix_and_metric_are_files_and_entries(tmp_path):
    """Adding a configuration, a traffic mix and a per-layer metric adds
    files and BENCHMARK.json entries; no harness file changes."""
    root = make_root(tmp_path)
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/tiny-ivf.json").read_text())
    cfg["name"] = "tiny-ivf-wide"
    cfg["index"]["nprobe"] = 16
    (root / "bench/configs/tiny-ivf-wide.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny-slow.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 40.0, "clients": 4,
         "zipf_alpha": 1.2, "noise": 0.05, "pool": 64}))
    (root / "bench/metrics/answered.py").write_text(
        "def read(ctx):\n    return float((ctx.window.status == 0).sum())\n")
    bm["configs"].append({"name": "tiny-ivf-wide", "source": "test",
                          "file": "bench/configs/tiny-ivf-wide.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "wide.slow", "config": "tiny-ivf-wide",
                            "traffic": "tiny-slow", "chips": 1,
                            "why": "test"})
    bm["per_layer"].append({"name": "answered", "unit": "requests",
                            "better": "higher", "source": "host_clock",
                            "layer": "client (benchmark)",
                            "moves": "p99_ms", "workloads": ["wide.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.load_cell("wide.slow", root)
    assert cell.config["index"]["nprobe"] == 16
    assert cell.traffic["rate_per_s"] == 40.0
    out = harness.run("wide.slow", 2**31 + 3, 0.5, True, root=root,
                      require_chip=False, compile_cache=False,
                      log=lambda _: None)
    assert out["correct"] is True
    assert out["metrics"]["answered"]["value"] == out["attempted"] == 20
    assert list(out)[-1] == "checks"


def test_a_reader_serves_every_suffix_of_its_stem():
    assert spec.reader("device_idle.steady") is not None
    assert spec.reader("device_idle.bulk").__file__.endswith(
        "device_idle.py")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such_metric.steady")


def _run_py(cwd, cell):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "ALLOW_MULTIPLE_LIBTPU_LOAD")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_without_a_tpu_and_prints_no_result():
    cell = spec.benchmark()["workloads"][0]["name"]
    p = _run_py(ROOT, cell)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, spec.benchmark()["workloads"][0]["name"])
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
