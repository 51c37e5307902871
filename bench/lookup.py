"""Find a cell's configuration, traffic mix, generator and metric readers by
the names ``BENCHMARK.json`` gives them.

A configuration is the file its entry names; a traffic mix is
``traffic/<name>.json``; a data generator is ``generators/<name>.py`` with
``generate(n, dim, seed)``; a per-layer metric is ``metrics/<name>.py`` with
``read(readings)``.  Adding any of them means adding files and entries,
never editing one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise LookupError(f"no file {path.relative_to(REPO)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise LookupError(f"no {what} file {path.relative_to(REPO)}")
    return json.loads(path.read_text())


def load_benchmark(repo: Path = REPO) -> dict:
    return _load_json(repo / "BENCHMARK.json", "benchmark")


def traffic(name: str) -> dict:
    return _load_json(BENCH / "traffic" / f"{name}.json", f"traffic mix {name!r}")


def generator(name: str):
    return _load_module(BENCH / "generators" / f"{name}.py", name).generate


def metric_reader(name: str):
    return _load_module(BENCH / "metrics" / f"{name}.py", name).read


def applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def cell(bench: dict, workload: str, repo: Path = REPO) -> dict:
    """Everything one run of ``workload`` needs, resolved by name."""
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise LookupError(f"no workload {workload!r} in BENCHMARK.json ({names})")
    w = found[0]
    entry = [c for c in bench["configs"] if c["name"] == w["config"]]
    if not entry:
        raise LookupError(f"workload {workload!r} names no known config {w['config']!r}")
    return {
        "name": workload,
        "chips": w["chips"],
        "config": _load_json(repo / entry[0]["file"], f"config {w['config']!r}"),
        "traffic": traffic(w["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if applies(m, workload)],
    }
