"""End-to-end index pipeline (paper §4): preprocessing -> overlap estimation
-> decision-making -> forest construction.

The supported entry point is the ``repro.api.OverlapIndex`` facade
(``OverlapIndex.build(x, cfg)`` / ``OverlapIndex.baseline(x, cfg)``), which
wraps the implementations here:

  build_index_core(x, cfg)     — the paper's proposed method (registry
                                 overlap heuristics: VBM / DBM / OBM / ...)
  build_baseline_core(x, cfg)  — the BCCF-tree baseline (single tree)
"""
from __future__ import annotations

import contextlib
import math
import time
import warnings
from collections.abc import Callable
from contextlib import AbstractContextManager
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.core.dbscan import dbscan, partitions_from_labels
from repro.core.decision import Partition, decide, rate_matrix
from repro.core.forest import ForestArrays, build_forest


@dataclass(frozen=True)
class IndexConfig:
    method: str = "vbm"  # vbm | dbm | obm
    xi_min: float = 0.4
    xi_max: float = 0.8
    eps: float = 1.0
    min_pts: int = 8
    c_max: int | None = None  # default sqrt(n)
    pivot_method: str = "gh"  # proposed trees use GH partitioning (§4.3)
    seed: int = 0
    dbscan_block: int = 1024


@dataclass
class BuildReport:
    config: IndexConfig
    n_objects: int = 0
    n_clusters: int = 0
    n_indexes: int = 0
    n_overlap_indexes: int = 0
    dbscan_distances: int = 0
    overlap_distances: int = 0
    tree_distances: int = 0
    tree_comparisons: int = 0
    wall_time_s: float = 0.0
    detail: dict[str, Any] = field(default_factory=dict)


def default_c_max(n: int) -> int:
    """Paper Def. 12: c_max = sqrt(n)."""
    return max(4, int(math.sqrt(n)))


def default_delta_capacity(n: int) -> int:
    """Per-index streaming delta-bucket capacity (stream/ingest.py).

    One c_max-sized tail per index keeps the search-time degradation of an
    un-merged delta bounded by roughly one extra bucket visit per selected
    index (the delta bucket is the same size as a full leaf), while giving
    the drift monitor a fill-fraction signal on the same scale the tree
    itself buckets at.  Floor of 64 so tiny seed sets still buffer usefully.
    """
    return max(64, default_c_max(n))


def _untimed(phase: str) -> AbstractContextManager:
    return contextlib.nullcontext()


def build_index_core(
    x, cfg: IndexConfig, *, span: Callable[[str], AbstractContextManager] = _untimed
) -> tuple[ForestArrays, BuildReport]:
    """The paper's pipeline: DBSCAN -> overlap -> decision -> forest.

    Each phase runs inside ``span(phase)``: ``dbscan``, ``overlap``,
    ``decide``, ``forest`` (the facade passes its registry's ``span``)."""
    t0 = time.perf_counter()
    x = np.asarray(x, np.float32)
    n = len(x)
    c_max = cfg.c_max or default_c_max(n)
    report = BuildReport(config=cfg, n_objects=n)

    # (i) preprocessing — DBSCAN and its partitions (§4.1, Algorithm 1)
    with span("dbscan"):
        res = dbscan(x, cfg.eps, cfg.min_pts, block=cfg.dbscan_block)
        pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)
    report.dbscan_distances = res.distance_computations
    report.n_clusters = res.n_clusters

    # (ii) overlap estimation (§4.2), (iii) decision (§4.3)
    with span("overlap"):
        rates = rate_matrix(cfg.method, x, pivots, radii, assign)
    with span("decide"):
        groups, dstats = decide(
            x, pivots, radii, assign,
            method=cfg.method, xi_min=cfg.xi_min, xi_max=cfg.xi_max, rates=rates,
        )
    report.overlap_distances = dstats.distance_computations
    report.n_overlap_indexes = dstats.n_overlap_indexes

    # indexing — one BCCF tree per group, GH pivots (§4.3)
    with span("forest"):
        forest = build_forest(
            x, groups, c_max=c_max, pivot_method=cfg.pivot_method, seed=cfg.seed
        )
    report.n_indexes = forest.n_indexes
    report.tree_distances = forest.build_stats["tree_distances"]
    report.tree_comparisons = forest.build_stats["tree_comparisons"]
    report.wall_time_s = time.perf_counter() - t0
    report.detail = dict(
        decision=dstats.__dict__,
        dbscan_iterations=res.n_iterations,
        structure=forest.aggregate_structure(),
    )
    return forest, report


def build_baseline_core(
    x, cfg: IndexConfig | None = None
) -> tuple[ForestArrays, BuildReport]:
    """BCCF-tree baseline [5]: one recursive tree over all data.

    The documented baseline semantics is 2-means ('kmeans') pivot selection
    — that is what ``cfg=None`` builds.  An explicit ``cfg`` is HONORED,
    including its ``pivot_method`` (it used to be silently overridden with
    'kmeans'); a non-kmeans choice emits a UserWarning because the result is
    then a single-tree ablation, not the paper's BCCF baseline.
    """
    t0 = time.perf_counter()
    x = np.asarray(x, np.float32)
    n = len(x)
    if cfg is None:
        cfg = IndexConfig(pivot_method="kmeans")
    elif cfg.pivot_method != "kmeans":
        warnings.warn(
            f"build_baseline honors cfg.pivot_method={cfg.pivot_method!r}, but "
            "the documented BCCF baseline uses 'kmeans' 2-means pivots; pass "
            "pivot_method='kmeans' (or cfg=None) to reproduce the paper's "
            "baseline",
            UserWarning,
            stacklevel=3,
        )
    c_max = cfg.c_max or default_c_max(n)
    pivot = x.mean(axis=0).astype(np.float32)
    radius = float(np.sqrt(((x - pivot) ** 2).sum(-1)).max())
    groups = [Partition(members=np.arange(n), pivot=pivot, radius=radius)]
    forest = build_forest(
        x, groups, c_max=c_max, pivot_method=cfg.pivot_method, seed=cfg.seed
    )
    report = BuildReport(config=cfg, n_objects=n, n_clusters=1, n_indexes=1)
    report.tree_distances = forest.build_stats["tree_distances"]
    report.tree_comparisons = forest.build_stats["tree_comparisons"]
    report.wall_time_s = time.perf_counter() - t0
    report.detail = dict(structure=forest.aggregate_structure())
    return forest, report
