"""Shared benchmark utilities: datasets, configs, CSV + JSON artifacts."""
from __future__ import annotations

import json
import os
import platform
import sys
import time
from dataclasses import dataclass

import numpy as np

sys.path.insert(0, "src")  # allow `python -m benchmarks.run` without install

from repro.api import (  # noqa: E402
    Config,
    IndexConfig,
    LayoutConfig,
    ObsConfig,
    SearchConfig,
)
from repro.data.synthetic import tracking_like, ward_like  # noqa: E402

METHODS = ("dbm", "obm", "vbm")


@dataclass(frozen=True)
class BenchDataset:
    name: str
    x: np.ndarray
    eps: float
    min_pts: int
    xi_min: float
    xi_max: float
    c_max: int


def load_datasets(full: bool = False, smoke: bool = False) -> list[BenchDataset]:
    """Paper Table 1 datasets (synthetic stand-ins; --full = paper sizes,
    ``smoke`` = CI sizes that keep every code path but finish in seconds).

    eps / MinPts are re-derived for the synthetic generators with the same
    procedure the paper implies (k-dist elbow); the paper's absolute values
    (eps=248 / 91) are tied to its private data scales.
    """
    if smoke:
        n_track, n_ward = 3_000, 6_000
    elif full:
        n_track, n_ward = DB1_ROWS, 1_000_000
    else:
        n_track, n_ward = 12_000, 40_000
    ward = ward_like(n_ward)
    return [
        tracking_dataset(n_track),
        BenchDataset("WARD", ward, eps=2.0, min_pts=23, xi_min=0.4,
                     xi_max=0.8, c_max=max(4, int(np.sqrt(n_ward)))),
    ]


DB1_ROWS = 62_702  # the paper's DB1 tracking set (Table 1), 20-d


def tracking_dataset(n: int = DB1_ROWS) -> BenchDataset:
    """The DB1 tracking stand-in at ``n`` rows: eps 6.0, MinPts 16,
    xi 0.4 / 0.8, c_max = sqrt(n) (250 at the paper's 62,702 rows)."""
    return BenchDataset("Tracking", tracking_like(n), eps=6.0, min_pts=16,
                        xi_min=0.4, xi_max=0.8, c_max=max(4, int(np.sqrt(n))))


def index_config(ds: BenchDataset, method: str) -> IndexConfig:
    return IndexConfig(
        method=method, xi_min=ds.xi_min, xi_max=ds.xi_max,
        eps=ds.eps, min_pts=ds.min_pts, c_max=ds.c_max,
    )


def layout_config(shards: int = 1, route: bool = False) -> LayoutConfig:
    """Device layout for a bench run: single below 2 shards, else the
    sharded island layout — or, with ``route=True``, the routed layout
    (routing tier over the same islands).  The caller is responsible for
    forcing a host mesh via XLA_FLAGS before jax initializes."""
    if shards <= 1:
        return LayoutConfig()
    if route:
        return LayoutConfig(kind="routed", shards=shards)
    return LayoutConfig(kind="sharded", shards=shards)


def facade_config(
    ds: BenchDataset, method: str, *, shards: int = 1, route: bool = False,
    obs: bool = True, quantize: bool = False,
) -> Config:
    """Full Config tree for OverlapIndex.build over a bench dataset.
    ``obs=False`` disables the telemetry registry (overhead comparisons)."""
    return Config(
        index=index_config(ds, method),
        search=SearchConfig(quantize=quantize),
        layout=layout_config(shards, route),
        obs=ObsConfig(enabled=obs),
    )


def baseline_config(
    ds: BenchDataset, *, shards: int = 1, route: bool = False,
    obs: bool = True, quantize: bool = False,
) -> Config:
    """BCCF baseline config: documented 'kmeans' pivot semantics, explicit
    so the honored-pivot warning never fires in benchmarks."""
    import dataclasses

    return Config(
        index=dataclasses.replace(index_config(ds, "vbm"), pivot_method="kmeans"),
        search=SearchConfig(quantize=quantize),
        layout=layout_config(shards, route),
        obs=ObsConfig(enabled=obs),
    )


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.s = time.perf_counter() - self.t0


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


# ---------------------------------------------------------------------------
# Machine-readable artifacts: every benchmark run writes BENCH_<name>.json
# so the perf trajectory is diffable across commits (the CSV lines above are
# for eyeballs; these files are for tooling/CI).
# ---------------------------------------------------------------------------

_RECORDS: dict[str, list[dict]] = {}


def record(bench: str, name: str, **fields) -> None:
    """Append one datapoint to the ``bench`` artifact (written at exit of
    the benchmark's run() via ``write_artifact``)."""
    _RECORDS.setdefault(bench, []).append(dict(name=name, **fields))


def write_artifact(bench: str, meta: dict | None = None) -> str:
    """Write BENCH_<bench>.json into $REPRO_BENCH_DIR (default: CWD).

    Schema: {"bench", "meta": {backend, jax, numpy, python, unix_time},
    "records": [{"name", ...datapoint fields}]}.  Returns the path.
    """
    import jax

    out_dir = os.environ.get("REPRO_BENCH_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{bench}.json")
    payload = {
        "bench": bench,
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "numpy": np.__version__,
            "python": platform.python_version(),
            "unix_time": time.time(),
            **(meta or {}),
        },
        # pop: a second run() in the same process must not concatenate its
        # records onto this artifact's
        "records": _RECORDS.pop(bench, []),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    print(f"# wrote {path} ({len(payload['records'])} records)")
    return path


# ---------------------------------------------------------------------------
# BENCH-artifact history: the substrate of the rolling-median regression
# gate (benchmarks/check_regress.py).  One JSONL line per (run, dataset,
# method) keeps the us_per_query trajectory across CI runs; windowed medians
# over that series flag SUSTAINED regressions while staying blind to
# single-run noise (the HomebrewNLP wandblog early-warning idiom).
# ---------------------------------------------------------------------------


def history_entries(payload: dict) -> list[dict]:
    """Collapse one BENCH artifact payload into per-(dataset, method)
    history lines: the MEDIAN us_per_query across the run's k sweep (one
    scalar per series per run keeps the gate's window semantics simple).

    Sharded-layout records (``shards > 1``) get a ``/s<N>`` method suffix:
    tier-2 CI appends its 4-shard timings into the SAME history file as
    tier-1, and the suffix keeps them a separate gated series instead of
    corrupting the single-device medians.  Routed-layout records (the
    routing tier over the same islands; ``routed`` truthy on the record)
    get ``/r<N>`` instead — their timings include the routing prefix and
    must gate as their own series too."""
    by: dict[tuple[str, str], list[float]] = {}
    for r in payload.get("records", []):
        if "us_per_query" in r and "dataset" in r and "method" in r:
            method = str(r["method"])
            shards = int(r.get("shards", 1))
            if shards > 1:
                tag = "r" if r.get("routed") else "s"
                method = f"{method}/{tag}{shards}"
            key = (str(r["dataset"]), method)
            by.setdefault(key, []).append(float(r["us_per_query"]))
    t = float(payload.get("meta", {}).get("unix_time", 0.0))
    return [
        {
            "t": t,
            "bench": payload.get("bench", "?"),
            "dataset": ds,
            "method": m,
            "us_per_query": float(np.median(v)),
            "n_points": len(v),
        }
        for (ds, m), v in sorted(by.items())
    ]


def load_history(path: str) -> list[dict]:
    """Read a JSONL history file; a missing file is an empty history."""
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def append_history(path: str, entries: list[dict]) -> None:
    """Append history lines (see ``history_entries``) to a JSONL file."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "a") as f:
        for e in entries:
            f.write(json.dumps(e, sort_keys=True) + "\n")


def history_series(entries: list[dict]) -> dict[tuple[str, str], list[float]]:
    """(dataset, method) -> us_per_query series in file (= run) order."""
    series: dict[tuple[str, str], list[float]] = {}
    for e in entries:
        key = (str(e["dataset"]), str(e["method"]))
        series.setdefault(key, []).append(float(e["us_per_query"]))
    return series


def rolling_median(values: list[float], window: int) -> float:
    """Median of the newest ``window`` values (all of them when shorter)."""
    if not values:
        return float("nan")
    return float(np.median(values[-window:]))
