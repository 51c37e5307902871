"""Search efficiency (paper Fig. 21): average distance computations,
comparisons and wall time for 100 kNN queries at k in {5,10,15,20,50,100},
per heuristic vs the BCCF baseline, plus recall@k vs exact brute force.

Runs through the ``repro.api.OverlapIndex`` facade — one index object per
(dataset, method), one cached SearchPlan per (k, mode); the warm pass and
the timed pass hit the same compiled executor.

``--smoke`` shrinks datasets and the k sweep for CI; the artifact
(BENCH_search.json) is written either way so the perf trajectory stays
diffable across commits.

``--shards N`` runs the sweep under the sharded device layout (forest
bucket rows + delta buffers split over N devices, one shard_map island per
search) and HARD-GATES on divergence: every sharded result is compared
bitwise against the single-device layout on the same forest — any mismatch
exits non-zero.  On CPU the flag also forces a host mesh by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
initializes, so ``python -m benchmarks.bench_search --smoke --shards 4``
works on a laptop/CI runner with no extra environment.

``--route N`` runs the sweep under the ROUTED layout (the multi-host
routing tier over the same N-shard islands: replicated routing table,
per-query host pruning, cost-model fanout decision) and hard-gates every
result bitwise against BOTH the plain sharded fan-all layout and the
single-device layout.  Each record additionally carries the routing
tier's decision counts (targeted/fan-all batches, eligible and pruned
host totals, estimated cross-host bytes under either fanout), so the
artifact shows the work the router removed, per (dataset, method, k).
"""
from __future__ import annotations

import os
import sys

# Must run before ANY jax import (jax reads XLA_FLAGS once at init): give
# the process enough host devices for the requested shard/host count.
for _flag in ("--shards", "--route"):
    if _flag in sys.argv:
        _n = int(sys.argv[sys.argv.index(_flag) + 1])
        _flags = os.environ.get("XLA_FLAGS", "")
        if _n > 1 and "xla_force_host_platform_device_count" not in _flags:
            os.environ["XLA_FLAGS"] = (
                f"{_flags} --xla_force_host_platform_device_count={_n}".strip()
            )

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (
    METHODS,
    baseline_config,
    emit,
    facade_config,
    load_datasets,
    record,
    write_artifact,
)
from repro.compile_cache import enable_compile_cache
from repro.api import OverlapIndex
from repro.core import knn_exact

K_VALUES = (5, 10, 15, 20, 50, 100)
K_VALUES_SMOKE = (5, 20)
N_QUERIES = 100


def _queries(x: np.ndarray, n: int, seed: int = 7) -> np.ndarray:
    g = np.random.default_rng(seed)
    idx = g.choice(len(x), n, replace=False)
    return (x[idx] + 0.05 * x.std() * g.normal(size=(n, x.shape[1]))).astype(np.float32)


def _run_one(ix: OverlapIndex, q, k, mode):
    ix.search(q, k=k, mode=mode)  # warm: plan + shape specialization
    t0 = time.perf_counter()
    res = ix.search(q, k=k, mode=mode)
    dt = time.perf_counter() - t0
    return res, dt


def _router_counts(ix: OverlapIndex) -> dict:
    """Flat cumulative snapshot of metrics()['router'] (zeros when absent)
    so per-(k, mode) deltas can be attached to bench records."""
    rm = ix.metrics().get("router") or {}
    fan = rm.get("fanout") or {}
    eb = rm.get("est_bytes") or {}
    return dict(
        route_queries=int(rm.get("queries", 0)),
        route_eligible=int(rm.get("eligible_hosts", 0)),
        route_pruned=int(rm.get("pruned_hosts", 0)),
        route_targeted=int(fan.get("targeted", 0)),
        route_all=int(fan.get("all", 0)),
        route_bytes_targeted=float(eb.get("targeted", 0.0)),
        route_bytes_all=float(eb.get("all", 0.0)),
    )


def run(
    full: bool = False,
    out: dict | None = None,
    *,
    quantize: bool = False,
    smoke: bool = False,
    shards: int = 1,
    route: int = 0,
    obs: bool = True,
) -> None:
    """``quantize`` stores bucket members int8 on device.  Recall is
    reported either way, so the search's exactness (mode='all' vs brute
    force) is visible.

    ``shards > 1`` runs the sweep under the sharded layout and compares
    every result bitwise against a single-device index built over the same
    dataset (builds are deterministic, so the forests are identical) —
    divergence is a hard failure, not a warning.

    ``route > 1`` runs the sweep under the routed layout (routing tier
    over ``route`` shard islands) instead, gating bitwise against BOTH the
    fan-all sharded layout and the single-device layout, and attaches the
    router's per-sweep decision counts to every record.  Mutually
    exclusive with ``shards > 1``.
    """
    if route > 1 and shards > 1:
        raise SystemExit("--route and --shards are mutually exclusive")
    routed = route > 1
    n_hosts = route if routed else shards
    k_values = K_VALUES_SMOKE if smoke else K_VALUES
    diverged: list[str] = []
    for ds in load_datasets(full, smoke=smoke):
        q = _queries(ds.x, N_QUERIES)
        de, ie = knn_exact(jnp.asarray(ds.x), jnp.asarray(q), k=max(k_values))
        ie = np.asarray(ie)
        indexes = {
            method: OverlapIndex.build(
                ds.x, facade_config(
                    ds, method, shards=n_hosts, route=routed, obs=obs,
                    quantize=quantize,
                )
            )
            for method in METHODS
        }
        indexes["bccf"] = OverlapIndex.baseline(
            ds.x, baseline_config(
                ds, shards=n_hosts, route=routed, obs=obs, quantize=quantize,
            )
        )
        refs: dict = {}
        refs_fanall: dict = {}
        if n_hosts > 1:
            # single-device references for the bitwise divergence gate
            refs = {
                method: OverlapIndex.build(
                    ds.x, facade_config(ds, method, quantize=quantize)
                )
                for method in METHODS
            }
            refs["bccf"] = OverlapIndex.baseline(
                ds.x, baseline_config(ds, quantize=quantize)
            )
        if routed:
            # fan-all references: the plain sharded layout on the same mesh
            refs_fanall = {
                method: OverlapIndex.build(
                    ds.x, facade_config(
                        ds, method, shards=n_hosts, quantize=quantize,
                    )
                )
                for method in METHODS
            }
            refs_fanall["bccf"] = OverlapIndex.baseline(
                ds.x, baseline_config(
                    ds, shards=n_hosts, quantize=quantize
                )
            )
        for method, ix in indexes.items():
            mode = "all" if method == "bccf" else "forest"
            for k in k_values:
                r0 = _router_counts(ix) if routed else None
                res, dt = _run_one(ix, q, k, mode)
                stats = res.stats
                route_fields = {}
                if routed:
                    r1 = _router_counts(ix)
                    route_fields = {key: r1[key] - r0[key] for key in r1}
                if n_hosts > 1:
                    ref = refs[method].search(q, k=k, mode=mode)
                    if not (
                        np.array_equal(res.dists, ref.dists)
                        and np.array_equal(res.ids, ref.ids)
                    ):
                        diverged.append(f"{ds.name}/{method}/k{k}:single")
                if routed:
                    ref = refs_fanall[method].search(q, k=k, mode=mode)
                    if not (
                        np.array_equal(res.dists, ref.dists)
                        and np.array_equal(res.ids, ref.ids)
                    ):
                        diverged.append(f"{ds.name}/{method}/k{k}:fanall")
                recall = float(np.mean([
                    len(set(res.ids[i].tolist()) & set(ie[i, :k].tolist())) / k
                    for i in range(len(q))
                ]))
                derived = (
                    f"dataset={ds.name};method={method};k={k};"
                    f"dist={stats['distances'].mean():.0f};"
                    f"bound_dist={stats['bound_distances'].mean():.0f};"
                    f"cmp={stats['comparisons'].mean():.0f};"
                    f"buckets={stats['buckets_visited'].mean():.1f};"
                    f"recall={recall:.3f};time_ms={dt*1e3/len(q):.3f}"
                )
                if routed:
                    derived += (
                        f";route_targeted={route_fields['route_targeted']};"
                        f"route_all={route_fields['route_all']};"
                        f"route_pruned={route_fields['route_pruned']}"
                    )
                emit(f"search/{ds.name}/{method}/k{k}", dt * 1e6 / len(q), derived)
                record(
                    "search", f"{ds.name}/{method}/k{k}",
                    dataset=ds.name, method=method, k=k, shards=n_hosts,
                    routed=routed,
                    dist=float(stats["distances"].mean()),
                    bound_dist=float(stats["bound_distances"].mean()),
                    cmp=float(stats["comparisons"].mean()),
                    buckets=float(stats["buckets_visited"].mean()),
                    recall=recall,
                    us_per_query=dt * 1e6 / len(q),
                    **route_fields,
                )
                if out is not None:
                    out[f"{ds.name}/{method}/k{k}"] = {
                        "dist": float(stats["distances"].mean()),
                        "cmp": float(stats["comparisons"].mean()),
                        "recall": recall,
                        "ms_per_query": dt * 1e3 / len(q),
                    }
            emit(f"search/{ds.name}/{method}/plans", 0.0,
                 f"plan_cache={ix.plans.stats()}")
    write_artifact("search", meta=dict(
        full=full, smoke=smoke, quantize=quantize,
        shards=n_hosts, route=route, obs=obs,
    ))
    if diverged:
        layout = "routed" if routed else "sharded"
        raise SystemExit(
            f"{layout} search diverged from reference on {len(diverged)} "
            f"configurations: {', '.join(diverged)}"
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--quantize", action="store_true",
                    help="int8 bucket member storage (device_forest knob)")
    ap.add_argument("--shards", type=int, default=1,
                    help="run under the sharded device layout (N devices on "
                    "the 'model' axis) and hard-gate bitwise vs single")
    ap.add_argument("--route", type=int, default=0,
                    help="run under the ROUTED layout (routing tier over N "
                    "shard islands) and hard-gate bitwise vs fan-all AND "
                    "single; records carry routing decision counts")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the telemetry registry (repro.obs) — for "
                    "measuring the metrics layer's own overhead")
    a = ap.parse_args()
    enable_compile_cache()
    run(full=a.full, quantize=a.quantize,
        smoke=a.smoke, shards=a.shards, route=a.route, obs=not a.no_obs)
