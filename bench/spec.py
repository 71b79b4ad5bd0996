"""Find a cell's parts by name: the benchmark's entries, its configuration
file, its traffic file, the metric readers and the peak table.

Everything is looked up under a checkout ``root``; adding a
configuration, a traffic mix or a metric adds a file and an entry in
``BENCHMARK.json``, and edits nothing here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bm['workloads']]})")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            with open(Path(root) / c["file"], encoding="utf-8") as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    path = Path(root) / "bench" / "traffic" / f"{name}.json"
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def metrics_of(bm: dict, cell: str, trace: bool) -> list:
    """The metric entries a cell reports: its end-to-end metrics without
    tracing, its per-layer metrics with it.  An entry without a
    ``workloads`` key belongs to every cell (end-to-end) or to every cell
    that reports the end-to-end metric it moves (per-layer)."""
    e2e = [m for m in bm["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(name: str, root: Path = ROOT):
    """The module that reads metric ``name``: ``bench/metrics/<name>.py``,
    or the file of its stem before the first dot, which serves every
    suffix (``device_idle.py`` reads ``device_idle.steady``)."""
    base = Path(root) / "bench" / "metrics"
    for stem in (name, name.split(".")[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{stem.replace('.', '_').replace('-', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for metric {name!r} under {base}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(Path(root) / "bench" / "peaks.json", encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
