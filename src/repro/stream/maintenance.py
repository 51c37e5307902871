"""Overlap-driven online index maintenance — closing the loop the paper
leaves static.

The paper computes VBM/DBM/OBM overlap rates ONCE, at build time, to decide
the partition layout (§4.2-4.3).  Under streaming ingest the geometry
drifts: delta appends shift centroids and inflate radii, so the overlap
structure the layout was optimized for stops being true.  This module
re-evaluates the paper's own heuristics (core/overlap.py) on the *updated*
geometry — exact post-ingest centroids and conservative radius upper bounds
maintained incrementally by stream/ingest.py — and, past configurable ξ
thresholds, schedules host-side per-index rebuilds (core/bccf.build_tree)
that absorb the delta into a fresh tree and are swapped in atomically.

Trigger taxonomy (``DriftReport.reasons``):

  overlap   max_j rate[i, j] >= xi_rebuild — the updated geometry crossed
            the same kind of threshold the build-time decision stage uses;
            the index's layout is no longer what the heuristic would choose.
  drift     rate[i, j] rose by >= drift_margin over the build-time baseline
            (relative trigger; off unless drift_margin is set).
  fill      delta buffer fill fraction >= fill_rebuild — search degradation
            bound (one over-full tail bucket per selected index).
  overflow  capacity-rejected appends recorded — standing trigger, the
            rejected points are waiting to be re-ingested.
  wasted    MEASURED waste: ``OverlapIndex.explain`` attribution reported
            >= wasted_rebuild of the visits into this index's buckets as
            wasted (no member survived into any final top-k).  Unlike the
            geometry triggers above this one is evidence from executed
            queries, fed in via ``note_wasted``; off unless wasted_rebuild
            is set AND explain() runs.

Rebuilds never drop queries: the new forest is built OFF to the side on the
host while the old (device forest, delta) pair keeps serving; the swap
installs the new device arrays, a fresh delta, and re-ingests the surviving
delta members of untouched indexes in one step (tests assert search is
exact across the swap boundary).  DIMS's serve-under-redistribution design
(PAPERS.md) is the pattern; FITing-Tree's buffered inserts bound the cost.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from time import perf_counter
from typing import Any

import numpy as np

from repro.core.bccf import build_tree
from repro.core.forest import ForestArrays, swap_trees
from repro.core.overlap import (
    get_overlap_method,
    max_neighbor_rate,
    overlap_matrix,
)
from repro.stream.ingest import DeltaBuffer, pull_delta_meta, updated_geometry

import jax.numpy as jnp


@dataclass(frozen=True)
class MaintenanceConfig:
    """ξ thresholds and rebuild knobs for the drift monitor.

    (The facade expresses the same knobs as ``repro.api.StreamConfig``;
    this struct remains the engine-room parameter set.)
    """

    method: str = "dbm"  # any registered overlap method, re-evaluated online
    xi_rebuild: float = 0.8  # absolute overlap rate forcing repartition
    drift_margin: float | None = None  # optional rise-over-baseline trigger
    fill_rebuild: float = 0.75  # delta fill fraction forcing a merge-rebuild
    wasted_rebuild: float | None = None  # measured wasted-visit share trigger
    pivot_method: str = "gh"
    c_max: int | None = None  # default: keep the forest's bucket capacity
    seed: int = 1


@dataclass
class DriftReport:
    """One monitor evaluation: updated rates vs baseline + fired triggers."""

    rates_baseline: np.ndarray  # (I, I) build-time overlap rates
    rates: np.ndarray  # (I, I) rates on the updated geometry
    centers: np.ndarray  # (I, D) post-ingest centroids
    radii: np.ndarray  # (I,) conservative radius upper bounds
    fill: np.ndarray  # (I,) delta fill fraction
    dropped: np.ndarray  # (I,) capacity-rejected appends
    triggers: list[int] = field(default_factory=list)
    reasons: dict[int, list[str]] = field(default_factory=dict)

    @property
    def should_rebuild(self) -> bool:
        return bool(self.triggers)


def object_assignment(
    forest: ForestArrays, delta_host: dict[str, np.ndarray] | None, n_total: int
) -> np.ndarray:
    """(N,) object id -> owning index, across main buckets and delta tails
    (the OBM monitor needs a full assignment, Def. 11's denominator)."""
    assign = np.full(n_total, -1, np.int64)
    m = forest.bucket_mask
    assign[forest.bucket_ids[m]] = np.repeat(forest.bucket_index, m.sum(axis=1))
    if delta_host is not None:
        for i in range(forest.n_indexes):
            c = int(delta_host["count"][i])
            if c:
                assign[delta_host["ids"][i, :c]] = i
    return assign


def _rates(
    method: str,
    centers: np.ndarray,
    radii: np.ndarray,
    x: np.ndarray | None,
    assign: np.ndarray | None,
) -> np.ndarray:
    if get_overlap_method(method).needs_objects and (x is None or assign is None):
        raise ValueError(
            f"object-based drift monitoring ({method!r}) needs the dataset "
            "+ assignment"
        )
    return np.asarray(
        overlap_matrix(
            method,
            jnp.asarray(centers, jnp.float32),
            jnp.asarray(radii, jnp.float32),
            x=None if x is None else jnp.asarray(x, jnp.float32),
            assign=None if assign is None else jnp.asarray(assign),
        )
    )


class OverlapMonitor:
    """Re-evaluates the paper's overlap heuristic as the geometry drifts.

    The baseline matrix is captured from the forest's build-time geometry;
    ``check`` recomputes the same heuristic on the post-ingest geometry
    (stream/ingest.updated_geometry) and classifies every index against the
    ξ thresholds.  Cheap by construction: O(I^2) rate math on incrementally
    maintained sums — no scan of the raw data (except OBM, which is defined
    over objects and receives them explicitly).
    """

    def __init__(
        self,
        forest: ForestArrays,
        cfg: MaintenanceConfig,
        *,
        x: np.ndarray | None = None,
    ):
        self.cfg = cfg
        self.forest = forest
        needs_objects = get_overlap_method(cfg.method).needs_objects
        assign = None
        if needs_objects:
            if x is None:
                raise ValueError(
                    f"object-based monitor ({cfg.method!r}) needs the dataset "
                    "at construction"
                )
            assign = object_assignment(forest, None, len(x))
        self.rates_baseline = _rates(
            cfg.method, forest.index_centers, forest.index_radii, x, assign
        )
        n_idx = forest.n_indexes
        # measured-waste accumulators (explain() attribution evidence);
        # recreated-with-the-monitor after a rebuild, so they reset exactly
        # when the geometry they judged stops existing
        self.wasted_visits = np.zeros(n_idx, np.int64)  # wasted, by visited
        self.attr_visits = np.zeros(n_idx, np.int64)  # all, by visited

    # minimum attributed visits into an index before the measured-waste
    # trigger may fire — a handful of explain()ed queries must not force a
    # rebuild off noise
    WASTED_MIN_VISITS = 16

    def note_wasted(
        self, wasted_pair: np.ndarray, visited_pair: np.ndarray
    ) -> None:
        """Fold one ``ExplainReport``'s (visited, home) pair matrices into
        the lifetime accumulators (rows: visited index)."""
        self.wasted_visits += np.asarray(wasted_pair, np.int64).sum(axis=1)
        self.attr_visits += np.asarray(visited_pair, np.int64).sum(axis=1)

    def wasted_share(self) -> np.ndarray:
        """(I,) fraction of attributed visits into each index that were
        wasted (0 where nothing was attributed yet)."""
        return self.wasted_visits / np.maximum(self.attr_visits, 1)

    def check(
        self, delta: DeltaBuffer, *, x: np.ndarray | None = None
    ) -> DriftReport:
        cfg = self.cfg
        needs_objects = get_overlap_method(cfg.method).needs_objects
        centers_d, radii_d = updated_geometry(delta)
        centers = np.asarray(centers_d)
        radii = np.asarray(radii_d)
        host = pull_delta_meta(delta, ids=needs_objects)
        assign = None
        if needs_objects:
            if x is None:
                raise ValueError(
                    f"object-based drift check ({cfg.method!r}) needs the dataset"
                )
            assign = object_assignment(self.forest, host, len(x))
        rates = _rates(cfg.method, centers, radii, x, assign)

        capd = delta.capacity
        fill = host["count"].astype(np.float64) / max(capd, 1)
        report = DriftReport(
            rates_baseline=self.rates_baseline,
            rates=rates,
            centers=centers,
            radii=radii,
            fill=fill,
            dropped=host["dropped"],
        )
        worst = np.asarray(max_neighbor_rate(jnp.asarray(rates)))
        worst_base = np.asarray(max_neighbor_rate(jnp.asarray(self.rates_baseline)))
        for i in range(len(radii)):
            why = []
            # Fire only on overlap the CURRENT layout doesn't account for:
            # if the post-rebuild baseline itself sits at/above the rate, a
            # per-index rebuild cannot reduce it (that pair needs a merge —
            # the decision stage's job, not maintenance's) and re-firing
            # would churn rebuilds forever.
            if worst[i] >= cfg.xi_rebuild and worst[i] > worst_base[i] + 1e-6:
                why.append("overlap")
            if cfg.drift_margin is not None and (
                worst[i] - worst_base[i] >= cfg.drift_margin
            ):
                why.append("drift")
            if fill[i] >= cfg.fill_rebuild:
                why.append("fill")
            if host["dropped"][i] > 0:
                why.append("overflow")
            if (
                cfg.wasted_rebuild is not None
                and self.attr_visits[i] >= self.WASTED_MIN_VISITS
                and self.wasted_share()[i] >= cfg.wasted_rebuild
            ):
                why.append("wasted")
            if why:
                report.triggers.append(i)
                report.reasons[i] = why
        return report


def rebuild_indexes(
    forest: ForestArrays,
    delta: DeltaBuffer,
    x_all: np.ndarray,
    triggers: list[int],
    cfg: MaintenanceConfig,
) -> tuple[ForestArrays, dict[str, Any]]:
    """Rebuild the triggered indexes' BCCF trees with their delta absorbed.

    Host-side (the build path of any production vector store): per index,
    gather main members from the (fresh — see swap_trees) host tree copies
    plus the delta members, run ``core.bccf.build_tree``, recompute exact
    centroid/radius, and swap everything in via ``forest.swap_trees``.
    Returns (new ForestArrays, rebuild stats).
    """
    host = pull_delta_meta(delta, ids=True)
    replacements = {}
    centers = forest.index_centers.copy()
    radii = forest.index_radii.copy()
    n_absorbed = 0
    t0 = perf_counter()
    for gi in triggers:
        main_ids = np.concatenate(
            [np.asarray(m, np.int64) for m in forest.trees[gi].bucket_members]
        )
        c = int(host["count"][gi])
        d_ids = host["ids"][gi, :c].astype(np.int64)
        members = np.concatenate([main_ids, d_ids])
        n_absorbed += c
        pts = x_all[members]
        replacements[gi] = build_tree(
            pts,
            members,
            c_max=cfg.c_max or forest.c_max,
            pivot_method=cfg.pivot_method,
            seed=cfg.seed + gi,
        )
        center = pts.mean(axis=0).astype(np.float32)
        centers[gi] = center
        radii[gi] = float(np.sqrt(((pts - center) ** 2).sum(-1)).max())
    new_forest = swap_trees(
        forest, x_all, replacements, index_centers=centers, index_radii=radii
    )
    stats = dict(
        n_rebuilt=len(triggers),
        n_absorbed=n_absorbed,
        rebuild_distances=sum(t.counters.distances for t in replacements.values()),
        wall_time_s=perf_counter() - t0,
    )
    return new_forest, stats
