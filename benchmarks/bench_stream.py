"""Streaming subsystem benchmark: ingest throughput, delta-search overhead,
and maintenance/rebuild cost for the ingest → monitor → rebuild lifecycle
(src/repro/stream/).

Measured per dataset:
  * ingest      — device routing+append throughput (points/s), steady state;
  * search      — ms/query over forest+delta at increasing delta fill, vs
                  the empty-delta baseline (the degradation the fixed
                  capacity bounds);
  * maintain    — drift-monitor evaluation cost and, when triggered, the
                  host rebuild + hot-swap wall time;
  * exactness   — mode='all' over forest+delta vs brute force over every
                  object ingested so far (hard gate, not a statistic).

``--smoke`` shrinks sizes for CI (runs in well under a minute on CPU and
exercises every code path including at least one rebuild swap).

Artifacts: CSV lines on stdout (benchmarks/common.emit) and a
machine-readable BENCH_stream.json (common.write_artifact).
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit, record, write_artifact
from repro.compile_cache import enable_compile_cache
from repro.api import Config, IndexConfig, OverlapIndex, StreamConfig
from repro.core import knn_exact

K = 10
N_QUERIES = 64


def _queries(x: np.ndarray, n: int, seed: int = 7) -> np.ndarray:
    g = np.random.default_rng(seed)
    idx = g.choice(len(x), min(n, len(x)), replace=False)
    return (x[idx] + 0.05 * x.std() * g.normal(size=(len(idx), x.shape[1]))).astype(
        np.float32
    )


def _drifting_batches(
    n_total: int, batch: int, dim: int, seed: int
) -> list[np.ndarray]:
    """IoT-style arrival: clustered points whose centers wander over time,
    plus a slowly growing bridge between regions (the overlap-drift driver)."""
    g = np.random.default_rng(seed)
    centers = g.normal(size=(6, dim)) * 12.0
    drift = g.normal(size=(6, dim))
    drift /= np.linalg.norm(drift, axis=1, keepdims=True)
    out = []
    t = 0.0
    remaining = n_total
    while remaining > 0:
        m = min(batch, remaining)
        lab = g.integers(0, 6, m)
        pts = centers[lab] + t * drift[lab] * 2.0 + g.normal(size=(m, dim))
        out.append(pts.astype(np.float32))
        remaining -= m
        t += 1.0
    return out


def _search_ms(sf: OverlapIndex, q: np.ndarray, *, mode: str) -> float:
    sf.search(q, k=K, mode=mode)  # warm: plan + shape specialization
    t0 = time.perf_counter()
    d, i, s = sf.search(q, k=K, mode=mode)  # SearchResult unpacks (host sync)
    return (time.perf_counter() - t0) * 1e3 / len(q)


def run(smoke: bool = False) -> None:
    if smoke:
        n_seed, n_stream, batch, dim, capacity = 1_500, 1_500, 256, 8, 256
    else:
        n_seed, n_stream, batch, dim, capacity = 20_000, 40_000, 1_024, 12, 2_048

    batches = _drifting_batches(n_stream, batch, dim, seed=11)
    x0 = np.concatenate(_drifting_batches(n_seed, n_seed, dim, seed=3))

    with Timer() as t_build:
        sf = OverlapIndex.build(x0, Config(
            index=IndexConfig(method="vbm", eps=2.5, min_pts=8),
            stream=StreamConfig(
                capacity=capacity, monitor_method="dbm",
                xi_rebuild=0.6, fill_rebuild=0.7,
            ),
        ))
    emit("stream/build", t_build.s * 1e6,
         f"n={n_seed};indexes={sf.forest.n_indexes};buckets={sf.forest.n_buckets}")
    record("stream", "build", n_seed=n_seed, indexes=sf.forest.n_indexes,
           buckets=sf.forest.n_buckets, wall_s=t_build.s)

    sf.check()  # allocate the (empty) delta so the baseline includes its scan
    q = _queries(x0, N_QUERIES)
    base_ms = _search_ms(sf, q, mode="forest")
    emit("stream/search_empty_delta", base_ms * 1e3, f"k={K};delta_fill=0")
    record("stream", "search_empty_delta", ms_per_query=base_ms, fill=0.0)

    # --- streaming loop ----------------------------------------------------
    ingest_s = 0.0
    maint_s = 0.0
    n_rebuilds0 = len(sf.rebuild_log)
    for bi, xb in enumerate(batches):
        t0 = time.perf_counter()
        sf.ingest(xb)
        jnp.asarray(sf.delta.count).block_until_ready()
        ingest_s += time.perf_counter() - t0

        t0 = time.perf_counter()
        report = sf.maintain()
        maint_s += time.perf_counter() - t0
        if report.triggers:
            emit("stream/rebuild", sf.rebuild_log[-1]["wall_time_s"] * 1e6,
                 f"batch={bi};triggers={len(report.triggers)};"
                 f"reasons={sorted(set(r for v in report.reasons.values() for r in v))}")
            record("stream", "rebuild", batch=bi,
                   triggers=len(report.triggers),
                   absorbed=sf.rebuild_log[-1]["n_absorbed"],
                   wall_s=sf.rebuild_log[-1]["wall_time_s"])
        if bi == len(batches) // 2:
            fill = float(np.asarray(sf.delta.count).sum()) / (
                sf.capacity * sf.forest.n_indexes)
            mid_ms = _search_ms(sf, q, mode="forest")
            emit("stream/search_mid_stream", mid_ms * 1e3,
                 f"k={K};delta_fill={fill:.3f};overhead={mid_ms / base_ms:.2f}x")
            record("stream", "search_mid_stream", ms_per_query=mid_ms, fill=fill)

    pts_per_s = n_stream / max(ingest_s, 1e-9)
    emit("stream/ingest", ingest_s * 1e6 / n_stream,
         f"n={n_stream};points_per_s={pts_per_s:.0f}")
    record("stream", "ingest", n=n_stream, points_per_s=pts_per_s,
           wall_s=ingest_s)
    emit("stream/maintain", maint_s * 1e6 / len(batches),
         f"checks={len(batches)};rebuilds={len(sf.rebuild_log) - n_rebuilds0}")
    record("stream", "maintain", checks=len(batches),
           rebuilds=len(sf.rebuild_log) - n_rebuilds0, wall_s=maint_s)

    # --- hard exactness gate ----------------------------------------------
    x_all = sf.x_all
    qf = _queries(x_all, N_QUERIES, seed=13)
    d, ids, stats = sf.search(qf, k=K, mode="all")
    de, _ = knn_exact(jnp.asarray(x_all), jnp.asarray(qf), k=K)
    # f32 ||q||^2+||x||^2-2qx expansion: ~1e-3 at these coordinate scales
    np.testing.assert_allclose(
        np.asarray(d), np.asarray(de), rtol=1e-3, atol=1e-3
    )
    end_ms = _search_ms(sf, qf, mode="forest")
    emit("stream/search_end", end_ms * 1e3,
         f"k={K};n_total={sf.n_total};exact=1;overhead={end_ms / base_ms:.2f}x")
    record("stream", "search_end", ms_per_query=end_ms, n_total=sf.n_total,
           exact=True)
    write_artifact("stream", meta=dict(
        smoke=smoke, n_seed=n_seed, n_stream=n_stream, batch=batch,
        capacity=capacity, rebuilds=len(sf.rebuild_log),
    ))
    print(f"stream bench OK: {n_stream} ingested at {pts_per_s:.0f} pts/s, "
          f"{len(sf.rebuild_log)} rebuilds, final search exact")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    enable_compile_cache()
    run(smoke=ap.parse_args().smoke)
