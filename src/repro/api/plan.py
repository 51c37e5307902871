"""Search planning: one cached, jitted executor per static-option tuple.

``knn_search`` used to be one big ``jax.jit`` whose cache was invisible —
every caller paid tracing whenever *any* static knob or operand shape moved,
and nobody could observe it.  The facade splits that into

  * ``PlanKey``     — the static options a compiled executor is specialized
                      on: ``(k, mode, beam, kernel, quantize, delta
                      capacity, shards)``;
  * ``SearchPlan``  — the key plus a ``jax.jit``-wrapped closure over the
                      layout backend's executor body (the single-device
                      ``core.knn.knn_search_impl`` or the sharded
                      ``distributed/knn_island.sharded_search`` island) with
                      those options baked in, and a *trace counter*
                      (incremented only while tracing, so tests can assert
                      "no re-trace");
  * ``PlanCache``   — the per-index table of plans with hit/miss counters,
                      bounded by ``max_plans`` with LRU eviction (an
                      unbounded cache leaked one compiled executor per
                      distinct option tuple forever).

Repeated ``OverlapIndex.search`` calls with stable options and shapes hit
the same plan and the same compiled executable: zero re-tracing.  A changed
query-batch shape re-specializes *within* the plan (jax's shape cache, the
trace counter records it); a changed option is a new plan.
"""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import numpy as np

from repro.core.knn import (
    DeltaView,
    DeviceForest,
    SearchStats,
    knn_search_explain_impl,
    knn_search_impl,
)


class PlanKey(NamedTuple):
    """Static options one compiled search executor is specialized on."""

    k: int
    mode: str
    beam: int
    kernel: bool
    quantize: bool
    delta_capacity: int | None  # None: no delta phase compiled in
    shards: int = 1  # device layout (1: single; >1: sharded island)
    # explain plans additionally return core.knn.VisitRows (the visited-row
    # evidence obs/attribution.py decodes); a separate plan keeps the
    # normal search executor's output contract — and its compiled
    # artifact — untouched
    explain: bool = False
    # routed layout only: the dispatch policy compiled into the executor
    # ('auto' | 'targeted' | 'all'); None on single/sharded layouts, so
    # their keys — and cached plans — are unchanged
    fanout: str | None = None


@dataclass
class SearchPlan:
    """A compiled search program for one ``PlanKey``.

    ``executor(device_forest, q, delta)`` returns the raw device 4-tuple
    ``(dists, ids, SearchStats, IslandStats | None)`` — the fourth element
    carries per-executor-island node-access counters (leading dim = shard
    count; the single layout reports one island) for the telemetry layer,
    or ``None`` on the legacy backend-less path.  Explain plans
    (``key.explain``) append a fifth element, ``core.knn.VisitRows`` — the
    per-query visited-row evidence the attribution layer decodes.  Routed
    executors (``key.fanout`` set) append one further trailing element,
    ``distributed.router.RouterStats`` — the facade unpacks by position
    from the front and treats any extra trailing element as router
    telemetry.  The first operand is whatever the backend's
    ``search_operands`` wraps (the bare forest, or (forest, table) on the
    routed layout).
    ``traces`` counts actual
    jax traces (option tuple is fixed, so a trace means a new operand
    shape/dtype); ``calls`` counts executions through this plan.
    """

    key: PlanKey
    executor: Callable[..., tuple[Any, ...]] = None  # set below
    traces: int = 0
    calls: int = 0


def _build_plan(key: PlanKey, backend=None) -> SearchPlan:
    plan = SearchPlan(key=key)
    if backend is None:
        # no layout backend (legacy/direct use): the single-device executor,
        # normalized to the 4-tuple contract (no island breakdown)
        if key.explain:
            def body(forest: DeviceForest, q, delta: DeltaView | None):
                d, i, s, rows = knn_search_explain_impl(
                    forest, q, k=key.k, mode=key.mode, beam=key.beam,
                    kernel=key.kernel, delta=delta,
                )
                return d, i, s, None, rows
        else:
            def body(forest: DeviceForest, q, delta: DeltaView | None):
                d, i, s = knn_search_impl(
                    forest, q, k=key.k, mode=key.mode, beam=key.beam,
                    kernel=key.kernel, delta=delta,
                )
                return d, i, s, None
    else:
        body = (
            backend.explain_body(key) if key.explain
            else backend.search_body(key)
        )

    def _impl(forest: DeviceForest, q, delta: DeltaView | None):
        # Runs only while jax traces (compiled executions skip python):
        # the counter is exactly the number of specializations.
        plan.traces += 1
        return body(forest, q, delta)

    plan.executor = jax.jit(_impl)
    return plan


class PlanCache:
    """Per-``OverlapIndex`` table of search plans, LRU-bounded.

    ``max_plans`` caps how many compiled executors stay alive; exceeding it
    evicts the least-recently-used plan (its executable is dropped for jax
    to GC — a re-request simply recompiles).  The default is far above any
    sane working set of option tuples, so eviction only fires on
    pathological churn (e.g. a distinct k per call)."""

    def __init__(self, max_plans: int = 64, *, registry=None) -> None:
        if max_plans < 1:
            raise ValueError(f"max_plans={max_plans} must be >= 1")
        self._plans: OrderedDict[PlanKey, SearchPlan] = OrderedDict()
        self.max_plans = max_plans
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.evicted_traces = 0  # lifetime traces of plans no longer cached
        # optional repro.obs.Registry: hit/miss/eviction counters register
        # into the owner's telemetry namespace alongside the local ints
        self._obs = registry

    def _count(self, name: str) -> None:
        if self._obs is not None:
            self._obs.counter(name).inc()

    def plan(self, key: PlanKey, backend=None) -> SearchPlan:
        got = self._plans.get(key)
        if got is None:
            self.misses += 1
            self._count("plan_cache.misses")
            got = self._plans[key] = _build_plan(key, backend)
            if len(self._plans) > self.max_plans:
                # evict least recently used — but fold its trace count into
                # the lifetime accumulator first: stats()["traces"] reports
                # compilations PAID, which eviction must not un-count
                _, evicted = self._plans.popitem(last=False)
                self.evicted_traces += evicted.traces
                self.evictions += 1
                self._count("plan_cache.evictions")
        else:
            self.hits += 1
            self._count("plan_cache.hits")
            self._plans.move_to_end(key)
        return got

    def __len__(self) -> int:
        return len(self._plans)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._plans

    def keys(self) -> tuple[PlanKey, ...]:
        return tuple(self._plans)

    def stats(self) -> dict[str, int]:
        return dict(
            plans=len(self._plans),
            max_plans=self.max_plans,
            hits=self.hits,
            misses=self.misses,
            evictions=self.evictions,
            # lifetime compilations: live plans + plans eviction dropped
            # (evicted_traces keeps the total monotone across LRU churn)
            traces=self.evicted_traces
            + sum(p.traces for p in self._plans.values()),
        )


@dataclass(frozen=True)
class SearchResult:
    """Structured result of ``OverlapIndex.search``: true L2 distances,
    global object ids (-1 where fewer than k objects were reachable), and
    the paper's per-query cost instrumentation — as host numpy.

    Iterates as ``(dists, ids, stats)`` so legacy triple-unpacking keeps
    working.
    """

    dists: np.ndarray  # (Q, k')
    ids: np.ndarray  # (Q, k')
    stats: dict[str, Any]
    plan: SearchPlan = field(repr=False, compare=False, default=None)

    def __iter__(self):
        yield from (self.dists, self.ids, self.stats)

    @property
    def k(self) -> int:
        return int(self.dists.shape[1])


def stats_to_host(s: SearchStats) -> dict[str, Any]:
    """SearchStats device arrays -> the host dict shape the benchmarks and
    the legacy ``knn_search_host`` wrapper always reported.

    ONE ``jax.device_get`` of the whole NamedTuple: per-field ``np.asarray``
    issued six blocking device->host transfers (each waiting on the same
    executor) where a single batched fetch does."""
    host = jax.device_get(s)
    return {
        "buckets_visited": host.buckets_visited,
        "distances": host.distances,
        "bound_distances": host.bound_distances,
        "padded_distances": host.padded_distances,
        "comparisons": host.comparisons,
        "steps": int(host.steps),
        "topk_inserts": host.topk_inserts,
    }
