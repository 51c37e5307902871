"""``OverlapIndex`` — the one owner object for the paper's whole pipeline.

DBSCAN -> overlap estimation (registry heuristics) -> decision -> BCCF
forest -> routed kNN search -> streaming ingest -> overlap-driven online
maintenance -> persistence -> serving datastore, behind one facade:

    from repro.api import Config, IndexConfig, OverlapIndex

    ix = OverlapIndex.build(x, Config(index=IndexConfig(method="vbm", eps=2.0)))
    res = ix.search(q, k=10)          # SearchResult: dists / ids / stats
    ix.ingest(batch)                  # streaming writes (delta buffers)
    ix.maintain()                     # overlap-drift monitor + hot rebuilds
    ix.save("index.npz")              # rebuild-free restart ...
    ix2 = OverlapIndex.load("index.npz")  # ... bitwise-identical searches
    ds = ix.to_datastore(values)      # kNN-LM serving datastore

Internally the facade owns: the host ``ForestArrays`` (+ fresh tree
copies), the device ``DeviceForest`` upload (quantized per config), the
streaming ``DeltaBuffer`` (allocated lazily on first ingest), the overlap
drift monitor, and a ``PlanCache`` of compiled search executors — repeated
searches with stable options/shapes never re-trace.  Every search is
compiled through that cache and the layout backend (``api/executor.py``):
the facade is the one public read path.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import persist
from repro.api.config import (
    SEARCH_MODES,
    Config,
    ConfigError,
    LayoutConfig,
    as_index_config,
)
from repro.api.executor import make_backend
from repro.api.plan import PlanCache, PlanKey, SearchResult, stats_to_host
from repro.core.forest import ForestArrays
from repro.core.knn import DeviceForest, member_bytes, member_format, route_points
from repro.core.overlap import get_overlap_method, overlap_matrix
from repro.core.pipeline import (
    BuildReport,
    IndexConfig as _LegacyIndexConfig,
    build_baseline_core,
    build_index_core,
    default_delta_capacity,
)
from repro.kernels.bucket_scan import tile_bytes
from repro.obs import (
    EventLog,
    Registry,
    TraceContext,
    TraceSampler,
    current_trace,
    events_path_from_env,
    use_trace,
)
from repro.obs.attribution import ExplainReport, attribute_visits
from repro.stream.ingest import (
    DeltaBuffer,
    alloc_delta,
    delta_view,
    pull_delta_meta,
)


def _as_config(cfg: Config | _LegacyIndexConfig | None) -> Config:
    if cfg is None:
        return Config()
    if isinstance(cfg, Config):
        return cfg
    if isinstance(cfg, _LegacyIndexConfig):  # incl. the validated subclass
        return Config(index=as_index_config(cfg))
    raise ConfigError(
        f"expected a repro.api.Config (or an IndexConfig for the index node), "
        f"got {type(cfg).__name__}"
    )


def _registry(cfg: Config) -> Registry:
    events_path = cfg.obs.events_path or events_path_from_env()
    return Registry(
        enabled=cfg.obs.enabled,
        window=cfg.obs.window,
        events=None if events_path is None else EventLog(
            events_path,
            max_bytes=cfg.obs.events_max_bytes,
            backups=cfg.obs.events_backups,
        ),
    )


def _check_data(x) -> np.ndarray:
    x = np.asarray(x, np.float32)
    if x.ndim != 2 or len(x) == 0:
        raise ConfigError(
            f"dataset must be a non-empty (N, D) array, got shape {x.shape}"
        )
    return x


class OverlapIndex:
    """Lifecycle owner for one overlap-optimized forest (see module doc)."""

    # -- construction --------------------------------------------------------
    def __init__(self, *args, **kwargs):
        raise TypeError(
            "OverlapIndex is constructed via OverlapIndex.build(x, cfg), "
            ".baseline(x, cfg), or .load(path)"
        )

    @classmethod
    def _wire(
        cls,
        x: np.ndarray,
        forest: ForestArrays,
        cfg: Config,
        report: BuildReport,
        *,
        n_total: int | None = None,
        delta: DeltaBuffer | None = None,
        capacity: int | None = None,
        rebuild_log: list[dict[str, Any]] | None = None,
        monitor_baseline: np.ndarray | None = None,
        clamp_layout: bool = False,
        obs: Registry | None = None,
    ) -> "OverlapIndex":
        self = object.__new__(cls)
        self.cfg = cfg
        self.forest = forest
        self.build_report = report
        self.backend = make_backend(cfg.layout, clamp=clamp_layout)
        self._x_parts: list[np.ndarray] = [x]
        self._x_cache: np.ndarray | None = x
        self.n_total = len(x) if n_total is None else n_total
        self._device: DeviceForest | None = None  # lazy (see .device)
        self.capacity = (
            capacity
            or cfg.stream.capacity
            or default_delta_capacity(self.n_total)
        )
        # backend-resident buffers (padded + sharded under the sharded
        # layout); every host-facing consumer reads the .delta property
        self._delta: DeltaBuffer | None = (
            None if delta is None else self.backend.place_delta(delta)
        )
        self._ingest_exec = None  # lazy jitted ingest (see _ingest_executor)
        self._ingest_traces = 0
        self._ingest_calls = 0
        self.monitor = None
        if delta is not None:
            self.monitor = self._make_monitor()
            if monitor_baseline is not None:
                # restore the baseline captured at save time: recomputing it
                # over the restart-time dataset would shift object-based
                # trigger decisions mid-stream
                self.monitor.rates_baseline = np.asarray(monitor_baseline)
        # one telemetry registry per index: every layer below (plan cache,
        # spans, ingest/maintenance counters, per-island node accesses)
        # registers here; ``metrics()`` is the single snapshot of it all
        self.obs = _registry(cfg) if obs is None else obs
        self._set_index_gauges()
        # per-request tracing: self-sampled searches (cfg.obs.trace_sample)
        # get their own TraceContext; an ambient context installed by a
        # caller (ServeEngine) always wins
        self._tracer = TraceSampler(cfg.obs.trace_sample)
        self._searches_since_swap = 0  # maintenance.rebuild_age gauge
        self.plans = PlanCache(registry=self.obs)
        self.rebuild_log: list[dict[str, Any]] = rebuild_log or []
        return self

    @classmethod
    def build(
        cls, x, cfg: Config | _LegacyIndexConfig | None = None
    ) -> "OverlapIndex":
        """The paper's proposed pipeline (§4): overlap-optimized forest."""
        cfg = _as_config(cfg)
        x = _check_data(x)
        # the registry comes first, so that the build's phases are spans:
        # build/dbscan, build/overlap, build/decide, build/forest
        obs = _registry(cfg)
        with obs.span("build"):
            forest, report = build_index_core(x, cfg.index, span=obs.span)
        return cls._wire(x, forest, cfg, report, obs=obs)

    @classmethod
    def baseline(
        cls, x, cfg: Config | _LegacyIndexConfig | None = None
    ) -> "OverlapIndex":
        """The BCCF-tree baseline: one tree over all data.  With no config
        this builds the paper's documented 2-means baseline; an explicit
        config is honored (see ``build_baseline_core``)."""
        x = _check_data(x)
        if cfg is None:
            forest, report = build_baseline_core(x, None)
            cfg = Config(index=as_index_config(report.config))
        else:
            cfg = _as_config(cfg)
            forest, report = build_baseline_core(x, cfg.index)
        return cls._wire(x, forest, cfg, report)

    # -- dataset bookkeeping -------------------------------------------------
    @property
    def x_all(self) -> np.ndarray:
        if self._x_cache is None or len(self._x_cache) != self.n_total:
            self._x_cache = np.concatenate(self._x_parts)
            self._x_parts = [self._x_cache]
        return self._x_cache

    @property
    def n_indexes(self) -> int:
        return self.forest.n_indexes

    @property
    def device(self) -> DeviceForest:
        """Device upload of the forest, quantized per ``cfg.search`` and
        placed per ``cfg.layout`` (sharded bucket rows under the sharded
        backend).

        Lazy: host-only consumers (build reports, structure rollups, the
        construction benchmarks) never pay the upload — and build wall time
        measures the build, not the transfer.  First search/ingest uploads,
        inside the span ``build/upload`` whatever span asked for it, with
        the int8 step (``cfg.search.quantize``) as ``build/upload/quantize``.
        """
        if self._device is None:
            with self.obs.span_at("build/upload"):
                self._device = jax.block_until_ready(self.backend.upload_forest(
                    self.forest, quantize=self.cfg.search.quantize,
                    span=self.obs.span,
                ))
            self._set_member_gauges()
        return self._device

    def _set_index_gauges(self) -> None:
        """The forest's shape as gauges: ``index.indexes``,
        ``index.overlap_indexes`` and ``index.buckets``."""
        f = self.forest
        self.obs.gauge("index.indexes").set(f.n_indexes)
        self.obs.gauge("index.overlap_indexes").set(int(f.is_overlap_index.sum()))
        self.obs.gauge("index.buckets").set(f.n_buckets)

    def _set_member_gauges(self) -> None:
        """The uploaded member store as gauges: ``index.member_bytes`` (its
        device bytes) and ``index.member_format`` (``core.knn.MEMBER_FORMATS``:
        0 f32, 1 int8 exact, 2 int8 scaled)."""
        self.obs.gauge("index.member_bytes").set(member_bytes(self._device))
        self.obs.gauge("index.member_format").set(member_format(self._device))

    @property
    def delta(self) -> DeltaBuffer | None:
        """LOGICAL (unpadded) view of the streaming delta buffers — what the
        drift monitor, persistence, and introspection consume.  Identical to
        the device-resident buffers under the single layout; the sharded
        layout slices off the shard-alignment pad rows."""
        if self._delta is None:
            return None
        return self.backend.logical_delta(self._delta, self.forest.n_indexes)

    @property
    def device_delta(self) -> DeltaBuffer | None:
        """Backend-resident delta buffers exactly as the executors see them
        (padded + sharded under the sharded layout) — the serving datastore
        rides on these so its searches reuse the same placement."""
        return self._delta

    # -- read path: planner + cached executors -------------------------------
    def _plan_key(self, k, mode, beam, explain) -> PlanKey:
        # per-call overrides get the SAME validation the config tree does —
        # a bad k/beam/mode must fail here with an actionable error, not
        # deep inside the jitted executor (and never poison the plan cache)
        sc = self.cfg.search
        key = PlanKey(
            k=sc.k if k is None else int(k),
            mode=sc.mode if mode is None else mode,
            beam=sc.beam if beam is None else int(beam),
            quantize=sc.quantize,
            delta_capacity=None if self._delta is None else self.capacity,
            shards=self.backend.shards,
            explain=explain,
            # routed layout: the dispatch policy is a static compile knob —
            # None elsewhere keeps single/sharded plan keys unchanged
            fanout=(
                self.cfg.layout.routing.fanout
                if self.backend.kind == "routed" else None
            ),
        )
        if key.k < 1:
            raise ConfigError(f"search k={key.k} must be >= 1 neighbors")
        if key.mode not in SEARCH_MODES:
            raise ConfigError(
                f"search mode {key.mode!r} is unknown; choose one of "
                f"{', '.join(SEARCH_MODES)}"
            )
        if key.beam < 1:
            raise ConfigError(f"search beam={key.beam} must be >= 1")
        return key

    def _search_planned(self, q, *, k=None, mode=None, beam=None, explain=False):
        """The plan_lookup and dispatch phases of one search, as spans under
        the caller's outer span (``search`` or ``explain``).  dispatch only
        enqueues the program: the wait for the device is ``_run``'s
        device_wait span.

        Returns the device values ``(d, i, SearchStats, IslandStats,
        RouterStats | None, plan)``; an explain plan appends its
        ``VisitRows`` and each query's routed index (``home``)."""
        with self.obs.span("plan_lookup"):
            key = self._plan_key(k, mode, beam, explain)
            plan = self.plans.plan(key, self.backend)
            plan.calls += 1
            delta = None if self._delta is None else delta_view(self._delta)
        with self.obs.span("dispatch"):
            qj = jnp.asarray(q, jnp.float32)
            outs = plan.executor(
                self.backend.search_operands(self.device), qj, delta
            )
            # (d, i, SearchStats, IslandStats[, VisitRows][, RouterStats])
            d, i, s, isl = outs[:4]
            n = 5 if explain else 4
            router = outs[n] if len(outs) > n else None
            if not explain:
                return d, i, s, isl, router, plan
            # home = the routed index, computed with the DEVICE routing op
            # so tie-breaks match the executor — on one device: the
            # compiler cannot partition a Pallas call over the sharded
            # layouts' replicated centers
            _, home = route_points(jnp.asarray(self.forest.index_centers), qj)
        return d, i, s, isl, router, plan, outs[4], home

    def _run(self, q, k, mode, beam, *, explain=False):
        """One search through the plan cache: ``_search_planned``, then the
        device_wait and copy_back spans.  Returns ``(SearchResult, isl,
        router, evidence)`` on the host.  ``isl``/``router`` (island and
        router telemetry) are fetched only when the registry is enabled,
        else ``None``; ``evidence`` is ``[VisitRows, home]`` for an explain
        plan, else empty.

        device_wait first requests the host copy of every output it will
        bring back, so each copy queues behind the program and starts as
        it ends, then blocks on the outputs; copy_back is ONE fetch of them
        all."""
        obs = self.obs
        d, i, s, isl, router, plan, *evidence = self._search_planned(
            q, k=k, mode=mode, beam=beam, explain=explain
        )
        # island/router stats only feed the registry
        tele = (isl, router) if obs.enabled else (None, None)
        outs = (d, i, s, evidence, tele)
        with obs.span("device_wait"):
            for a in jax.tree.leaves(outs):
                a.copy_to_host_async()
            jax.block_until_ready((d, i, s, isl, router, evidence))
        with obs.span("copy_back"):
            d, i, s, evidence, (isl, router) = self._fetch(outs)
            stats = stats_to_host(s)
        kk = min(plan.key.k, self.n_total)  # Def. 4: |X| <= k -> all
        if d.shape[1] > kk:
            d, i = d[:, :kk], i[:, :kk]
        res = SearchResult(dists=d, ids=i, stats=stats, plan=plan)
        return res, isl, router, evidence

    def _fetch(self, x):
        """ONE blocking device-to-host fetch of the pytree ``x`` (one
        ``jax.device_get``, ``None`` leaves fetch nothing), counted as 1
        into ``search.host_fetches`` and its arrays' ``nbytes`` into
        ``search.host_fetch_bytes``."""
        obs = self.obs
        if obs.enabled:
            obs.counter("search.host_fetches").inc()
            obs.counter("search.host_fetch_bytes").inc(
                sum(a.nbytes for a in jax.tree.leaves(x))
            )
        return jax.device_get(x)

    def _record_search(
        self, stats: dict[str, Any], isl, router=None, *, beam: int
    ) -> None:
        """Fold one search's host-side stats into the registry: fleet
        node-access counters plus the per-island breakdown the sharded
        executor reports (load balance across shards) — and, on the routed
        layout, the routing tier's dispatch telemetry.  ``isl`` and
        ``router`` are host values (fetched in the caller's copy_back).

        ``search.tile_bytes`` counts the member-tile bytes the compiled
        bucket scan brings in (``kernels.bucket_scan.tile_bytes`` from
        ``SearchStats.steps``), every step priced at the forest's tile,
        a delta phase's steps included."""
        obs = self.obs
        queries = len(stats["buckets_visited"])
        obs.counter("search.queries").inc(queries)
        for name in ("buckets_visited", "distances", "bound_distances", "topk_inserts"):
            obs.counter(f"search.{name}").inc(int(stats[name].sum()))
        _, cap, dim = self._device.bucket_x.shape
        obs.counter("search.tile_bytes").inc(tile_bytes(
            int(stats["steps"]), queries, beam, cap, dim,
            self._device.bucket_x.dtype.itemsize,
        ))
        if router is not None:
            mode = "targeted" if bool(router.targeted) else "all"
            obs.counter("router.queries").inc(len(router.eligible_hosts))
            obs.counter("router.eligible_hosts").inc(
                int(router.eligible_hosts.sum())
            )
            obs.counter("router.pruned_hosts").inc(
                int(router.pruned_hosts.sum())
            )
            obs.counter("router.fanout", mode=mode).inc(
                len(router.eligible_hosts)
            )
            obs.counter("router.est_bytes", mode="targeted").inc(
                int(router.wire_targeted)
            )
            obs.counter("router.est_bytes", mode="all").inc(
                int(router.wire_fanall)
            )
            obs.emit_event(
                {
                    "event": "router",
                    "fanout": mode,
                    "eligible_hosts": router.eligible_hosts.tolist(),
                    "pruned_hosts": int(router.pruned_hosts.sum()),
                    "est_bytes_targeted": float(router.wire_targeted),
                    "est_bytes_fanall": float(router.wire_fanall),
                },
                traced_only=True,
            )
        if isl is None:
            return
        method = self.cfg.index.method
        for s_id in range(isl.buckets_visited.shape[0]):
            for name in ("buckets_visited", "distances", "bound_distances"):
                obs.counter(
                    f"search.island.{name}", island=s_id, method=method
                ).inc(int(getattr(isl, name)[s_id].sum()))
            # traced requests additionally get a per-island point event in
            # their span tree (dropped outside a sampled trace: per-request
            # annotations must not bloat steady-state logs)
            obs.emit_event(
                {
                    "event": "island",
                    "island": s_id,
                    "buckets_visited": int(isl.buckets_visited[s_id].sum()),
                    "distances": int(isl.distances[s_id].sum()),
                },
                traced_only=True,
            )

    def search(
        self, q, *, k: int | None = None, mode: str | None = None,
        beam: int | None = None, trace: TraceContext | None = None,
    ) -> SearchResult:
        """kNN over forest + streaming delta.  Defaults come from
        ``cfg.search``; per-call overrides select (or create) the matching
        cached ``SearchPlan``.  Returns a host-side ``SearchResult``.

        ``trace`` joins this search to a caller-owned request trace; with
        no explicit context and no ambient one, ``cfg.obs.trace_sample``
        self-samples (the sampled search becomes its own trace root in the
        event log).  Tracing never touches the executors — traced and
        untraced searches return bitwise-identical results.
        """
        obs = self.obs
        ctx = trace
        if ctx is None and obs.enabled and current_trace() is None:
            ctx = self._tracer.maybe_trace()
        self._searches_since_swap += 1
        obs.gauge("maintenance.rebuild_age").set(self._searches_since_swap)
        with use_trace(ctx), obs.span("search"):
            res, isl, router, _ = self._run(q, k, mode, beam)
            with obs.span("record"):
                if obs.enabled:
                    self._record_search(res.stats, isl, router, beam=res.plan.key.beam)
        return res

    def explain(
        self, q, *, k: int | None = None, mode: str | None = None,
        beam: int | None = None, feed_monitor: bool = True,
    ) -> ExplainReport:
        """Search + overlap attribution: which bucket visits CONTRIBUTED a
        final top-k member, which were WASTED, and which (visited, home)
        partition pairs the waste charges to (``obs/attribution.py``).

        Runs the normal executor op sequence (a separate cached plan that
        additionally returns the visited-row evidence — the plain ``search``
        plan and its results are untouched, and ``report.result`` is
        bitwise-identical to ``search()``), then a host-side post-pass.
        Per query, contributing + wasted == ``stats['buckets_visited']``.
        Aggregates land in ``metrics()['overlap_health']`` and — with
        ``feed_monitor`` (default) — in the drift monitor's measured-waste
        accumulators (``StreamConfig.wasted_rebuild`` trigger).
        """
        obs = self.obs
        with obs.span("explain"):
            res, isl, router, (rows, home) = self._run(
                q, k, mode, beam, explain=True
            )
            if obs.enabled:
                self._record_search(res.stats, isl, router, beam=res.plan.key.beam)
            with obs.span("attribute"):
                report = self._attribute(rows, res.ids, home)
        report.result = res
        if obs.enabled:
            obs.counter("explain.queries").inc(report.queries)
            obs.counter("explain.contributing").inc(
                int(report.contributing.sum())
            )
            obs.counter("explain.wasted").inc(int(report.wasted.sum()))
            jj, ii = np.nonzero(report.wasted_pair)
            for j_v, i_h in zip(jj.tolist(), ii.tolist()):
                obs.counter(
                    "explain.wasted_pair", visited=j_v, home=i_h
                ).inc(int(report.wasted_pair[j_v, i_h]))
        if feed_monitor and self.monitor is not None:
            self.monitor.note_wasted(report.wasted_pair, report.visited_pair)
        return report

    def _attribute(self, rows, result_ids, home) -> ExplainReport:
        """Host-side decode of one explain run's ``VisitRows`` (see
        ``obs.attribution.attribute_visits`` for the semantics)."""
        forest = self.forest
        S = self.backend.shards
        method = self.cfg.stream.monitor_method
        rates = None
        if self.monitor is not None:
            rates = self.monitor.rates_baseline
        elif not get_overlap_method(method).needs_objects:
            rates = np.asarray(overlap_matrix(
                method,
                jnp.asarray(forest.index_centers, jnp.float32),
                jnp.asarray(forest.index_radii, jnp.float32),
            ))
        delta_ids = delta_count = None
        if self._delta is not None:
            meta = pull_delta_meta(self.delta, ids=True)
            delta_ids, delta_count = meta["ids"], meta["count"]
        return attribute_visits(
            order=rows.order,
            visits=rows.visits,
            dorder=rows.dorder,
            dvisits=rows.dvisits,
            result_ids=result_ids,
            home=home,
            n_indexes=forest.n_indexes,
            bucket_index=forest.bucket_index,
            bucket_ids=forest.bucket_ids,
            bucket_mask=forest.bucket_mask,
            # global row = shard-local row + shard * PADDED per-shard rows
            main_rows_per_shard=-(-forest.n_buckets // S),
            delta_rows_per_shard=-(-forest.n_indexes // S),
            delta_ids=delta_ids,
            delta_count=delta_count,
            rates=rates,
            method=method,
        )

    # -- write path ----------------------------------------------------------
    def _ensure_delta(self) -> None:
        if self._delta is None:
            self._delta = self.backend.place_delta(
                alloc_delta(self.forest, self.capacity)
            )
            self.monitor = self._make_monitor()

    def _ingest_executor(self):
        """One jitted ingest program per index, wrapping the backend's body
        with a trace counter (the ingest twin of ``api.plan.SearchPlan``).
        The jit cache keys on (centers shape, delta shapes, batch shape) —
        all stable across rebuilds and, with ``_pad_batch``, across ragged
        tail chunks — so steady-state streaming never re-traces."""
        if self._ingest_exec is None:
            body = self.backend.ingest_body()

            def _impl(centers, delta, xb, ids, valid):
                self._ingest_traces += 1  # runs only while jax traces
                return body(centers, delta, xb, ids, valid)

            self._ingest_exec = jax.jit(_impl)
        return self._ingest_exec

    def _pad_batch(self, n: int) -> int:
        """Padded chunk length: next power of two, clamped to the chunk
        ceiling (the delta capacity).  Bounds the number of compiled ingest
        shapes at log2(capacity) while wasting < 2x lanes on ragged tails —
        pad rows ride the ``valid`` parking mechanism (accepted upfront,
        stored nowhere)."""
        p = 1
        while p < n:
            p <<= 1
        return min(p, self.capacity)

    def ingest_stats(self) -> dict[str, int]:
        """Observability for the write path: compiled-trace and call
        counters of the jitted ingest executor (tests assert no-retrace)."""
        return dict(traces=self._ingest_traces, calls=self._ingest_calls)

    def _make_monitor(self):
        from repro.stream.maintenance import OverlapMonitor

        needs_x = get_overlap_method(self.cfg.stream.monitor_method).needs_objects
        return OverlapMonitor(
            self.forest, self._maint_cfg(), x=self.x_all if needs_x else None
        )

    def _maint_cfg(self):
        from repro.stream.maintenance import MaintenanceConfig

        s = self.cfg.stream
        return MaintenanceConfig(
            method=s.monitor_method,
            xi_rebuild=s.xi_rebuild,
            drift_margin=s.drift_margin,
            fill_rebuild=s.fill_rebuild,
            wasted_rebuild=s.wasted_rebuild,
            pivot_method=s.pivot_method,
            c_max=s.c_max,
            seed=s.seed,
        )

    def ingest(self, xb) -> np.ndarray:
        """Insert a batch; returns the assigned global object ids.

        Chunks the batch to the per-index buffer capacity so a forced
        maintenance pass (emptying the destination buffers) always makes the
        retry succeed — ingestion cannot silently drop or livelock.
        """
        self._ensure_delta()
        xb = np.asarray(xb, np.float32)
        if xb.ndim != 2 or xb.shape[1] != self.forest.bucket_x.shape[2]:
            raise ConfigError(
                f"ingest batch must be (B, {self.forest.bucket_x.shape[2]}), "
                f"got shape {xb.shape}"
            )
        ids = np.arange(self.n_total, self.n_total + len(xb), dtype=np.int64)
        self._x_parts.append(xb)
        self.n_total += len(xb)
        self._x_cache = None
        with self.obs.span("ingest"):
            self.obs.counter("ingest.points").inc(len(xb))
            for lo in range(0, len(xb), self.capacity):
                self._ingest_chunk(
                    xb[lo : lo + self.capacity], ids[lo : lo + self.capacity]
                )
        return ids

    def _ingest_chunk(self, xc: np.ndarray, ic: np.ndarray) -> None:
        # Termination argument: a round that rejects any point force-rebuilds
        # every rejecting index, emptying its buffer into the main structure.
        # A retried point (chunk size <= buffer capacity) can only be
        # rejected again by re-routing to a DIFFERENT still-full buffer, and
        # each round empties at least one of those — so at most n_indexes
        # rounds before every point is accepted.  Retries flip the ``valid``
        # mask instead of slicing the batch, and ragged tail chunks pad up to
        # a power-of-two shape with rows parked invalid, so every round (and
        # every steady-state batch) reuses one compiled ingest program.
        b = len(xc)
        bp = self._pad_batch(b)
        if bp > b:
            xc = np.concatenate(
                [xc, np.zeros((bp - b, xc.shape[1]), xc.dtype)]
            )
            ic = np.concatenate([ic, np.full((bp - b,), -1, ic.dtype)])
        pending = np.zeros(bp, bool)
        pending[:b] = True
        xj, ij = jnp.asarray(xc), jnp.asarray(ic)
        run = self._ingest_executor()
        for _ in range(self.forest.n_indexes + 1):
            self._ingest_calls += 1
            with self.obs.span("dispatch"):
                self._delta, acc = run(
                    self.device.index_centers, self._delta, xj, ij,
                    jnp.asarray(pending),
                )
            pending &= ~np.asarray(acc)
            if not pending.any():
                return
            # capacity hit: force-rebuild the rejecting indexes, retry rest
            self.obs.counter("ingest.capacity_retries").inc()
            meta = pull_delta_meta(self.delta)
            full = [
                i for i in range(self.forest.n_indexes) if meta["dropped"][i] > 0
            ]
            self._rebuild(full)
        raise RuntimeError(
            "ingest chunk still rejected after rebuilding every full index — "
            "invariant violation, please report"
        )

    # -- maintenance ---------------------------------------------------------
    def check(self):
        """Overlap-drift evaluation only (no rebuild) -> DriftReport."""
        self._ensure_delta()
        with self.obs.span("check"):
            needs_x = get_overlap_method(
                self.cfg.stream.monitor_method
            ).needs_objects
            report = self.monitor.check(
                self.delta, x=self.x_all if needs_x else None
            )
        self.obs.counter("maintain.checks").inc()
        for i, f in enumerate(report.fill):
            self.obs.gauge("maintenance.delta_fill", index=i).set(float(f))
        for reasons in report.reasons.values():
            for why in reasons:
                self.obs.counter("maintain.triggers", reason=why).inc()
        return report

    def maintain(self):
        """Run the drift monitor; rebuild + hot-swap every triggered index.

        The swap is atomic: queries see the old (device, delta) pair or the
        new pair, never a partial state.  Returns the DriftReport.
        """
        with self.obs.span("maintain"):
            report = self.check()
            if report.triggers:
                self._rebuild(report.triggers, report)
        return report

    def _rebuild(self, triggers: list[int], report=None) -> None:
        if not triggers:
            return
        with self.obs.span("rebuild"):
            self._rebuild_impl(triggers, report)

    def _rebuild_impl(self, triggers: list[int], report) -> None:
        from repro.stream.maintenance import rebuild_indexes

        x_all = self.x_all
        new_forest, stats = rebuild_indexes(
            self.forest, self.delta, x_all, triggers, self._maint_cfg()
        )
        # Survivors — delta members of indexes NOT rebuilt — keep their
        # original buffers wholesale: a kept index keeps its center, so the
        # old buffer's pivot/radius bound is still valid verbatim.  A pure
        # device-side select (no host round-trip, no re-routing) that BY
        # CONSTRUCTION cannot overflow: each kept buffer moves into a fresh
        # buffer of the same capacity.  Rebuilt indexes start empty (their
        # members were absorbed into the new trees); ``dropped`` resets —
        # rejected points were never stored and their owners retry them.
        new_device = self.backend.upload_forest(
            new_forest, quantize=self.cfg.search.quantize, span=self.obs.span
        )
        fresh = alloc_delta(new_forest, self.capacity)
        keep = np.ones(self.forest.n_indexes, bool)
        keep[list(triggers)] = False
        old = self.delta  # logical view: survivor select is index-aligned
        n_migrated = int(np.asarray(old.count)[keep].sum())
        kj = jnp.asarray(keep)
        new_delta = self.backend.place_delta(fresh._replace(
            x=jnp.where(kj[:, None, None], old.x, fresh.x),
            ids=jnp.where(kj[:, None], old.ids, fresh.ids),
            count=jnp.where(kj, old.count, fresh.count),
            pivot=jnp.where(kj[:, None], old.pivot, fresh.pivot),
            radius=jnp.where(kj, old.radius, fresh.radius),
            sum_x=jnp.where(kj[:, None], old.sum_x, fresh.sum_x),
        ))

        # ---- atomic swap: a query sees the old pair or the new pair --------
        # per-shard barrier first: under the sharded layout every shard's new
        # arrays must be materialized before the swap becomes visible, so the
        # hot swap stays atomic (single layout: no-op)
        self.backend.barrier(new_device, new_delta)
        self.forest, self._device, self._delta = new_forest, new_device, new_delta
        self._set_index_gauges()
        self._set_member_gauges()
        self.monitor = self._make_monitor()
        stats["triggers"] = list(triggers)
        stats["reasons"] = dict(report.reasons) if report is not None else {}
        stats["n_migrated"] = n_migrated
        self.rebuild_log.append(stats)
        self._searches_since_swap = 0
        self.obs.gauge("maintenance.rebuild_age").set(0)
        self.obs.counter("maintain.rebuilds").inc(len(triggers))
        self.obs.counter("maintain.migrated").inc(n_migrated)
        self.obs.histogram("maintain.rebuild_wall_s").observe(
            stats["wall_time_s"]
        )

    # -- persistence ---------------------------------------------------------
    def save(self, path) -> str:
        """Serialize the WHOLE index (forest + host trees + delta + config +
        dataset) to one .npz; returns the path written.  A ``load`` of that
        file serves bitwise-identical searches without rebuilding."""
        return persist.save_state(self, path)

    @classmethod
    def load(cls, path, *, layout: LayoutConfig | None = None) -> "OverlapIndex":
        """Rebuild-free restart from ``save`` output.

        Snapshots store LOGICAL (host, unpadded) state, so they are
        layout-independent: ``layout`` re-shards the loaded index onto a
        different device layout than it was saved under (searches stay
        bitwise-identical).  Without an override the saved layout is used,
        clamped to the devices this host actually has.
        """
        st = persist.load_state(path)
        cfg = st["cfg"]
        if layout is not None:
            from dataclasses import replace

            cfg = replace(cfg, layout=layout)
        return cls._wire(
            np.asarray(st["x_all"], np.float32),
            st["forest"],
            cfg,
            st["build_report"],
            n_total=st["n_total"],
            delta=st["delta"],
            capacity=st["capacity"],
            rebuild_log=st["rebuild_log"],
            monitor_baseline=st["monitor_baseline"],
            clamp_layout=layout is None,
        )

    # -- serving -------------------------------------------------------------
    def to_datastore(
        self, values, *, stream_capacity: int = 0, quantized: bool | None = None
    ):
        """Wrap this index as a kNN-LM serving ``ForestDatastore``.

        ``values[i]`` is the token paired with object id ``i`` — one value
        per object currently in the index (``n_total``).  A live streaming
        delta rides along (its members stay retrievable and serve-side
        ``ingest_keys`` appends into the same buffers).  ``stream_capacity``
        preallocates a values tail for that many FUTURE serve-side inserts;
        ``quantized`` overrides ``cfg.search.quantize`` for the datastore's
        bucket storage.
        """
        from repro.serve.retrieval import datastore_from_index

        return datastore_from_index(
            self, values, stream_capacity=stream_capacity, quantized=quantized
        )

    # -- introspection -------------------------------------------------------
    def metrics(self) -> dict[str, Any]:
        """ONE nested telemetry snapshot of this index (JSON-serializable).

        Sections:
          build        the build's phase spans (``build``, ``build/dbscan``,
                       ``build/overlap``, ``build/decide``, ``build/forest``;
                       ``build/upload`` once the first search or ingest has
                       uploaded the forest) and the forest's shape (gauges
                       ``index.indexes``, ``index.overlap_indexes``,
                       ``index.buckets``);
          search       per-phase span histograms (``search``,
                       ``search/plan_lookup``, ``search/dispatch``,
                       ``search/device_wait``, ``search/copy_back``,
                       ``search/record``) with p50/p95/p99 seconds, the
                       node-access totals, and the device-to-host fetch
                       counters (``host_fetches``, ``host_fetch_bytes``);
          plan_cache   compiled-executor table counters (hits/misses/
                       evictions/lifetime traces);
          ingest       write-path counters (compiled traces, executor calls,
                       points ingested, capacity-retry rounds);
          maintenance  drift-monitor checks, per-reason trigger counts
                       (overlap/drift/fill/overflow), rebuild totals;
          islands      per-executor-island node-access counters — the
                       paper's cost currency (buckets_visited / distances /
                       bound_distances) per shard, one island on the single
                       layout;
          router       routing-tier dispatch telemetry (routed layout):
                       queries routed, eligible/pruned-host totals, per-mode
                       fanout counts (``router.fanout{mode=...}``),
                       estimated cross-host all-gather bytes for both
                       dispatch modes, and a host-side summary of the live
                       routing table (host member counts, worst inter-host
                       overlap rate);
          overlap_health  ``explain()`` attribution rollup: contributing vs
                       wasted visit totals, the wasted fraction, and the
                       per-(visited, home) wasted-pair counters — the live
                       evidence behind the paper's overlap argument;
          registry     the raw registry snapshot (every counter/gauge/
                       histogram, including span paths not listed above).

        ``Registry.to_prometheus()`` (or ``python -m repro.obs.export``)
        renders the registry section in Prometheus text format.

        With ``cfg.obs.enabled=False`` the structural sections (plan_cache,
        ingest traces/calls, rebuilds) remain — their counters predate the
        registry — and the registry-backed ones are empty.
        """
        obs = self.obs
        snap = obs.snapshot()
        counters = obs.counters()
        islands: dict[int, dict[str, int]] = {}
        triggers: dict[str, int] = {}
        wasted_pairs: dict[str, int] = {}
        for (name, labels), val in counters.items():
            if name.startswith("search.island."):
                lab = dict(labels)
                islands.setdefault(int(lab["island"]), {})[
                    name[len("search.island."):]
                ] = val
            elif name == "maintain.triggers":
                triggers[dict(labels).get("reason", "?")] = val
            elif name == "explain.wasted_pair":
                lab = dict(labels)
                wasted_pairs[f"{lab['visited']}->{lab['home']}"] = val
        contributing = obs.value("explain.contributing")
        wasted = obs.value("explain.wasted")
        table = getattr(self.backend, "table", None)
        router_table = None
        if table is not None:
            t = jax.device_get(table)
            router_table = {
                "hosts": int(t.host_counts.shape[0]),
                "host_counts": t.host_counts.tolist(),
                "max_rate": (
                    float(t.host_rates.max()) if t.host_rates.size else 0.0
                ),
            }
        return {
            "enabled": obs.enabled,
            "build": {
                "spans": {
                    k: v for k, v in snap["histograms"].items()
                    if k == "build" or k.startswith("build/")
                },
                **{
                    name: snap["gauges"].get(f"index.{name}")
                    for name in ("indexes", "overlap_indexes", "buckets",
                                 "member_bytes", "member_format")
                },
            },
            "search": {
                "spans": {
                    k: v for k, v in snap["histograms"].items()
                    if k == "search" or k.startswith("search/")
                },
                "queries": obs.value("search.queries"),
                "buckets_visited": obs.value("search.buckets_visited"),
                "distances": obs.value("search.distances"),
                "bound_distances": obs.value("search.bound_distances"),
                "topk_inserts": obs.value("search.topk_inserts"),
                "tile_bytes": obs.value("search.tile_bytes"),
                "host_fetches": obs.value("search.host_fetches"),
                "host_fetch_bytes": obs.value("search.host_fetch_bytes"),
            },
            "plan_cache": self.plans.stats(),
            "ingest": {
                **self.ingest_stats(),
                "points": obs.value("ingest.points"),
                "capacity_retries": obs.value("ingest.capacity_retries"),
            },
            "maintenance": {
                "checks": obs.value("maintain.checks"),
                "triggers": triggers,
                "rebuilds": len(self.rebuild_log),
                "indexes_rebuilt": obs.value("maintain.rebuilds"),
                "migrated": obs.value("maintain.migrated"),
                # searches served since the last rebuild swap (gauge twin:
                # maintenance.rebuild_age); delta_fill gauges live in the
                # registry section under maintenance.delta_fill{index=i}
                "rebuild_age": self._searches_since_swap,
            },
            "islands": islands,
            "router": {
                "queries": obs.value("router.queries"),
                "eligible_hosts": obs.value("router.eligible_hosts"),
                "pruned_hosts": obs.value("router.pruned_hosts"),
                "fanout": {
                    m: obs.value("router.fanout", mode=m)
                    for m in ("targeted", "all")
                },
                "est_bytes": {
                    m: obs.value("router.est_bytes", mode=m)
                    for m in ("targeted", "all")
                },
                "table": router_table,
            },
            "overlap_health": {
                "explained_queries": obs.value("explain.queries"),
                "contributing": contributing,
                "wasted": wasted,
                "wasted_fraction": (
                    wasted / (contributing + wasted)
                    if (contributing + wasted) else 0.0
                ),
                "wasted_pairs": wasted_pairs,
                "monitor_wasted_share": (
                    None if self.monitor is None
                    else self.monitor.wasted_share().tolist()
                ),
            },
            "registry": snap,
        }

    def structure(self) -> dict[str, Any]:
        """aggregate_structure + live delta occupancy (always fresh)."""
        s = self.forest.aggregate_structure()
        if self.delta is not None:
            s["delta_fill"] = np.asarray(self.delta.count).tolist()
        else:
            s["delta_fill"] = [0] * self.forest.n_indexes
        s["delta_capacity"] = self.capacity
        s["n_objects"] = self.n_total
        s["rebuilds"] = self.forest.build_stats.get("rebuilds", 0)
        return s

    def __repr__(self) -> str:
        return (
            f"OverlapIndex(n={self.n_total}, indexes={self.forest.n_indexes}, "
            f"buckets={self.forest.n_buckets}, method={self.cfg.index.method!r}, "
            f"delta={'on' if self._delta is not None else 'off'}, "
            f"layout={self.backend.kind}"
            f"{f'x{self.backend.shards}' if self.backend.shards > 1 else ''}, "
            f"plans={len(self.plans)})"
        )
