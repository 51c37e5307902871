"""One run of one cell: data, build, warm-up, the measured window, the
per-layer readings, the comparison with the reference, and the result line.

``run.py`` is the command; this module holds the steps so that the tests can
drive a run on the CPU with a small configuration and a broken timed path.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

import benchdata
import reference
from lookup import BENCH, REPO, metric_reader

# JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR says,
# else a fixed directory in the checkout, so that later runs find it.
CACHE_DIR = REPO / ".jax_cache"
CHECK_BATCHES = 32  # window batches compared with the reference, drawn from the seed
POOL_BATCHES = 4096  # distinct query batches generated before the window


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Batch:
    """What one search call in the window returned and counted."""

    latency_s: float
    steps: int
    buckets: np.ndarray  # (Q,) buckets visited per query
    distances: np.ndarray  # (Q,) real member distances per query
    ids: np.ndarray
    dists: np.ndarray
    pool_index: int


@dataclass
class Readings:
    """What the per-layer metric readers may read."""

    config: dict
    traffic: dict
    peaks: dict
    batches: list[Batch]
    spans: dict[str, tuple[int, float]] = field(default_factory=dict)  # path -> (count, seconds)
    compiles: int = 0
    trace: object = None  # devtrace.Summary of the traced window, or None


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, is kept: set-up then finds
    # all of them on the second run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, require_chip: bool) -> tuple[list, dict]:
    """The devices the run uses and their peaks; NoChip without a TPU."""
    import jax

    devs = jax.devices()
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
        kind = devs[0].device_kind
        if kind not in peaks:
            raise NoChip(f"device {kind!r} is not in bench/peaks.json")
    kind = devs[0].device_kind
    return devs[:chips], peaks.get(kind, {})


def program_config(cfg: dict):
    from repro.api import Config, IndexConfig, SearchConfig

    return Config(index=IndexConfig(**cfg["index"]), search=SearchConfig(**cfg.get("search", {})))


def _span(ix, path: str) -> tuple[int, float]:
    h = ix.obs.histogram(path)
    return h.count, h.total


def _compiles(ix) -> int:
    return ix.plans.stats()["traces"] + ix.ingest_stats()["traces"]


# program spans the per-layer readers may read (``repro.obs`` histogram paths)
SPANS = ("search/plan_lookup",)


def window(ix, pool: np.ndarray, traffic: dict, seconds: float) -> tuple[list[Batch], float]:
    """Closed loop of search batches for ``seconds``; every call ends in host
    arrays, so each latency is the whole call."""
    import jax

    k, mode = traffic["k"], traffic["mode"]
    batches: list[Batch] = []
    clock = time.perf_counter
    t_start = clock()
    t_end = t_start
    i = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        while t_end - t_start < seconds:
            q = pool[i % len(pool)]
            t = clock()
            with jax.profiler.TraceAnnotation("bench.search"):
                res = ix.search(q, k=k, mode=mode)
            t_end = clock()
            s = res.stats
            batches.append(Batch(t_end - t, s["steps"], s["buckets_visited"], s["distances"],
                                 res.ids, res.dists, i % len(pool)))
            i += 1
    return batches, t_end - t_start


def percentile(values: list[float], pct: float) -> float:
    """Linear-interpolated percentile of all values (numpy's default rule)."""
    return float(np.percentile(np.asarray(values, np.float64), pct))


def end_to_end(batches: list[Batch], window_s: float, setup_s: float, batch: int) -> dict[str, float]:
    lat = [b.latency_s for b in batches]
    return {
        "query_throughput": len(batches) * batch / window_s,
        "latency_p90_ms": 1e3 * percentile(lat, 90),
        "setup_s": setup_s,
    }


def sample(n_batches: int, seed: int) -> list[int]:
    """The window batches compared with the reference, drawn from the seed."""
    g = benchdata.rng(seed, "sample")
    return np.sort(g.choice(n_batches, min(CHECK_BATCHES, n_batches), replace=False)).tolist()


def check_answers(x: np.ndarray, pool: np.ndarray, batches: list[Batch], limits: dict, seed: int):
    """Compare a seeded sample of the window's answers with the reference.

    Returns (numbers, per-number check entries, queries checked, queries failed)."""
    x = np.asarray(x, np.float64)  # converted once for every batch's brute force
    xx = reference.sq_norms(x)
    readings, failed, checked = [], 0, 0
    for j in sample(len(batches), seed):
        b = batches[j]
        rows = reference.compare_rows(pool[b.pool_index], x, b.ids, b.dists, xx)
        readings.append({name: float(v.max()) if name != "bad_ids" else float(v.sum())
                         for name, v in rows.items()})
        bad = np.zeros(len(b.ids), bool)
        for name, lim in limits.items():
            bad |= rows[name] > lim
        failed += int(bad.sum())
        checked += len(b.ids)
    numbers = reference.merge(readings)
    return numbers, reference.check(numbers, limits), checked, failed


def prepare(cfg: dict, traffic: dict, seed: int):
    """The seed's data and query pool, and the index built over the data
    with every plan the window uses warmed: (x, pool, index)."""
    from repro.api import OverlapIndex

    x = benchdata.dataset(cfg, seed)
    pool = benchdata.queries(x, cfg["query_noise"], POOL_BATCHES, traffic["batch"],
                             benchdata.rng(seed, "queries"))
    warm = benchdata.queries(x, cfg["query_noise"], 1, traffic["batch"],
                             benchdata.rng(seed, "warmup"))[0]
    ix = OverlapIndex.build(x, program_config(cfg))
    for _ in range(2):  # the first call uploads and compiles (or loads) the plan
        ix.search(warm, k=traffic["k"], mode=traffic["mode"])
    return x, pool, ix


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
             *, require_chip: bool = True) -> dict:
    """One run; returns the result object (``correct``, metrics, device, ...)."""
    import jax

    cfg, traffic = cell["config"], cell["traffic"]
    devs, peaks = devices(cell["chips"], require_chip)
    enable_compile_cache()
    x, pool, ix = prepare(cfg, traffic, seed)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only: no per-call Python events
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    spans0 = {p: _span(ix, p) for p in SPANS}
    compiles0 = _compiles(ix)
    setup_s = time.perf_counter() - t0
    batches, window_s = window(ix, pool, traffic, seconds)
    compiles = _compiles(ix) - compiles0
    spans = {p: (_span(ix, p)[0] - spans0[p][0], _span(ix, p)[1] - spans0[p][1]) for p in SPANS}
    summary = None
    if trace:
        jax.profiler.stop_trace()
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)
    del ix  # the program's state is freed before the reference runs
    if trace:
        import devtrace

        summary = devtrace.summarize(devtrace.load(devtrace.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": memory_peak,
    }
    metrics: dict[str, dict] = {}
    out: dict = {}
    if trace:
        r = Readings(cfg, traffic, peaks, batches, spans, compiles, summary)
        for m in cell["per_layer"]:
            got = metric_reader(m["name"])(r)
            if got is None:
                continue
            value, extra = got if isinstance(got, tuple) else (got, {})
            metrics[m["name"]] = {"value": value, "unit": m["unit"], **extra}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            out["breakdown"] = summary.breakdown()
    else:
        e2e = end_to_end(batches, window_s, setup_s, traffic["batch"])
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    numbers, checks, checked, failed = check_answers(x, pool, batches, cfg["limits"], seed)
    correct = all(c["ok"] for c in checks.values())
    return {
        "correct": correct,
        "attempted": len(batches) * traffic["batch"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
        **out,
        "checked": checked,
        "checks": {name: {"value": c["value"], "limit": c["limit"]} for name, c in checks.items()},
    }


def report(result: dict) -> None:
    """Each compared number beside its limit as the last lines on stderr,
    then the result as the last line on stdout."""
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
