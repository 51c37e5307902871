"""Telemetry layer (repro.obs) tests: metric primitives, registry
snapshots, span nesting, JSONL event round-trip, the no-effect guarantee
(metrics-enabled search bitwise-identical to metrics-off), the facade's
``OverlapIndex.metrics()`` snapshot shape, and the plan-cache accounting
fixes that rode along (eviction keeps lifetime traces; ``stats_to_host``
is one batched device fetch)."""
import json
import math
import threading

import numpy as np
import pytest

from repro.api import Config, IndexConfig, ObsConfig, OverlapIndex, StreamConfig
from repro.api.executor import SingleDeviceBackend
from repro.api.plan import PlanCache, PlanKey, stats_to_host
from repro.obs import EventLog, Histogram, Registry, events_path_from_env


def _cfg(obs: bool = True, **obs_kw) -> Config:
    return Config(
        index=IndexConfig(
            method="vbm", eps=1.5, min_pts=8, xi_min=0.3, xi_max=0.7
        ),
        stream=StreamConfig(capacity=64),
        obs=ObsConfig(enabled=obs, **obs_kw),
    )


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    assert reg.value("c") == 5
    assert reg.value("never_touched") == 0
    reg.gauge("g").set(2.5)
    reg.gauge("g").add(-0.5)
    assert reg.snapshot()["gauges"]["g"] == 2.0


def test_counter_labels_are_distinct_series():
    reg = Registry()
    reg.counter("hits", method="dbm").inc(3)
    reg.counter("hits", method="obm").inc(7)
    assert reg.value("hits", method="dbm") == 3
    assert reg.value("hits", method="obm") == 7
    snap = reg.snapshot()["counters"]
    assert snap["hits{method=dbm}"] == 3
    assert snap["hits{method=obm}"] == 7


@pytest.mark.parametrize("n", [1, 2, 7, 100, 2048])
def test_histogram_percentiles_match_numpy(n):
    # while count <= window the windowed percentile must be EXACTLY
    # numpy's linear-interpolation percentile over everything observed
    g = np.random.default_rng(n)
    vals = g.normal(size=n) ** 2
    h = Histogram(window=2048)
    for v in vals:
        h.observe(v)
    for q in (0, 25, 50, 95, 99, 100):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(vals, q)), rel=1e-12
        )
    s = h.snapshot()
    assert s["count"] == n
    assert s["sum"] == pytest.approx(vals.sum())
    assert s["min"] == vals.min() and s["max"] == vals.max()


def test_histogram_windowing_drops_oldest():
    h = Histogram(window=4)
    for v in [100.0, 100.0, 1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    # window holds the newest 4 observations; lifetime extrema persist
    assert h.percentile(100) == 4.0
    assert h.snapshot()["max"] == 100.0
    assert h.snapshot()["count"] == 6
    assert h.snapshot()["window"] == 4


def test_histogram_empty_is_nan():
    s = Histogram().snapshot()
    assert s["count"] == 0
    assert math.isnan(s["p50"]) and math.isnan(s["min"])


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_records_paths():
    reg = Registry()
    with reg.span("search") as outer:
        assert outer == "search"
        with reg.span("plan_lookup") as inner:
            assert inner == "search/plan_lookup"
    with reg.span("search"):
        pass
    hists = reg.snapshot()["histograms"]
    assert hists["search"]["count"] == 2
    assert hists["search/plan_lookup"]["count"] == 1
    assert hists["search/plan_lookup"]["p50"] >= 0.0


def test_span_unwinds_and_records_on_exception():
    reg = Registry()
    with pytest.raises(RuntimeError):
        with reg.span("outer"):
            with reg.span("boom"):
                raise RuntimeError("phase failed")
    hists = reg.snapshot()["histograms"]
    # both spans recorded despite the raise, and the stack unwound fully
    assert hists["outer/boom"]["count"] == 1
    assert hists["outer"]["count"] == 1
    with reg.span("clean") as path:
        assert path == "clean"  # not "outer/clean" — stack is empty again


def test_span_stack_is_per_thread():
    reg = Registry()
    seen = {}

    def worker(name):
        with reg.span(name):
            with reg.span("inner") as p:
                seen[name] = p

    ts = [threading.Thread(target=worker, args=(f"t{i}",)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert seen == {f"t{i}": f"t{i}/inner" for i in range(4)}


def test_disabled_registry_is_inert():
    reg = Registry(enabled=False)
    reg.counter("c").inc(10)
    reg.gauge("g").set(3)
    reg.histogram("h").observe(1.0)
    with reg.span("s") as path:
        assert path is None
    snap = reg.snapshot()
    assert snap["enabled"] is False
    assert snap["counters"] == {} and snap["histograms"] == {}
    # null objects are shared singletons — no per-call allocation
    assert reg.counter("a") is reg.counter("b")


# ---------------------------------------------------------------------------
# events (JSONL)
# ---------------------------------------------------------------------------


def test_event_log_roundtrip(tmp_path):
    p = tmp_path / "events.jsonl"
    with EventLog(str(p)) as log:
        log.emit({"event": "custom", "x": 1})
        reg = Registry(events=log)
        with reg.span("search", method="vbm"):
            pass
    recs = EventLog.read(str(p))
    assert [r["event"] for r in recs] == ["custom", "span"]
    assert recs[1]["span"] == "search"
    assert recs[1]["labels"] == {"method": "vbm"}
    assert recs[1]["dur_s"] >= 0.0
    assert all("ts" in r for r in recs)
    # append mode: reopening adds, never truncates
    with EventLog(str(p)) as log:
        log.emit({"event": "later"})
    assert len(EventLog.read(str(p))) == 3


def test_events_path_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_OBS_EVENTS", raising=False)
    assert events_path_from_env() is None
    monkeypatch.setenv("REPRO_OBS_EVENTS", "/tmp/x.jsonl")
    assert events_path_from_env() == "/tmp/x.jsonl"


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_obs_config_validation():
    from repro.api import ConfigError

    with pytest.raises(ConfigError, match="window"):
        ObsConfig(window=0)
    with pytest.raises(ConfigError, match="events_path"):
        ObsConfig(events_path="")
    with pytest.raises(ConfigError, match="trace_sample"):
        ObsConfig(trace_sample=1.5)
    with pytest.raises(ConfigError, match="trace_sample"):
        ObsConfig(trace_sample=-0.1)
    with pytest.raises(ConfigError, match="events_max_bytes"):
        ObsConfig(events_max_bytes=0)
    with pytest.raises(ConfigError, match="events_backups"):
        ObsConfig(events_backups=-1)
    with pytest.raises(ConfigError, match="wasted_rebuild"):
        StreamConfig(wasted_rebuild=0.0)
    with pytest.raises(ConfigError, match="wasted_rebuild"):
        StreamConfig(wasted_rebuild=1.5)


# ---------------------------------------------------------------------------
# facade integration
# ---------------------------------------------------------------------------


def test_metrics_enabled_search_bitwise_identical(blob_data, tmp_path):
    q = np.asarray(blob_data[:8])
    idx_on = OverlapIndex.build(blob_data, _cfg(obs=True))
    idx_off = OverlapIndex.build(blob_data, _cfg(obs=False))
    r_on = idx_on.search(q, k=5)
    r_off = idx_off.search(q, k=5)
    assert np.array_equal(np.asarray(r_on.dists), np.asarray(r_off.dists))
    assert np.array_equal(np.asarray(r_on.ids), np.asarray(r_off.ids))
    assert idx_off.metrics()["enabled"] is False
    assert idx_off.metrics()["search"]["queries"] == 0
    # sampled tracing is host-side bookkeeping too: a fully traced search
    # (every request gets a span tree in the event log) returns the same
    # bits as the metrics-off search
    idx_tr = OverlapIndex.build(blob_data, _cfg(
        obs=True, trace_sample=1.0,
        events_path=str(tmp_path / "tr.jsonl"),
    ))
    r_tr = idx_tr.search(q, k=5)
    assert np.array_equal(np.asarray(r_tr.dists), np.asarray(r_off.dists))
    assert np.array_equal(np.asarray(r_tr.ids), np.asarray(r_off.ids))
    # explain() runs the identical op sequence plus host-side attribution:
    # its embedded result must match plain search() bitwise as well
    rep = idx_tr.explain(q, k=5)
    assert np.array_equal(np.asarray(rep.result.dists), np.asarray(r_off.dists))
    assert np.array_equal(np.asarray(rep.result.ids), np.asarray(r_off.ids))


def test_facade_metrics_snapshot_shape(blob_data):
    idx = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    idx.search(q, k=5)
    idx.search(q, k=5)
    g = np.random.default_rng(0)
    idx.ingest(g.normal(size=(16, blob_data.shape[1])).astype(np.float32))
    idx.check()
    m = idx.metrics()
    assert m["enabled"] is True
    # per-phase spans under the search root
    spans = m["search"]["spans"]
    for path in ("search", "search/plan_lookup", "search/dispatch",
                 "search/device_wait", "search/copy_back", "search/record"):
        assert spans[path]["count"] == 2, path
    assert m["search"]["queries"] == 16
    assert m["search"]["buckets_visited"] > 0
    assert m["search"]["bound_distances"] > 0
    # plan cache counters flow into the same registry AND the stats dict
    assert m["plan_cache"]["misses"] >= 1
    assert m["registry"]["counters"]["plan_cache.misses"] \
        == m["plan_cache"]["misses"]
    assert m["ingest"]["points"] == 16
    assert m["maintenance"]["checks"] == 1
    # single layout: exactly one island, carrying the paper's cost currency
    assert set(m["islands"]) == {0}
    isl = m["islands"][0]
    assert isl["buckets_visited"] == m["search"]["buckets_visited"]
    assert isl["distances"] == m["search"]["distances"]
    assert json.dumps(m["registry"])  # whole snapshot is JSON-serializable


def test_metrics_events_jsonl(blob_data, tmp_path):
    p = tmp_path / "spans.jsonl"
    idx = OverlapIndex.build(blob_data, _cfg(events_path=str(p)))
    idx.search(np.asarray(blob_data[:4]), k=3)
    spans = {r["span"] for r in EventLog.read(str(p))}
    assert "search" in spans and "search/dispatch" in spans


# ---------------------------------------------------------------------------
# plan-cache accounting satellites
# ---------------------------------------------------------------------------


def _fake_key(i: int) -> PlanKey:
    return PlanKey(k=i + 1, mode="exact", beam=4,
                   quantize=False, delta_capacity=None, shards=1)


# building a plan wraps the backend's body in jax.jit; nothing is traced
_BACKEND = SingleDeviceBackend()


def test_plan_cache_eviction_keeps_lifetime_traces():
    cache = PlanCache(max_plans=2)
    for i in range(4):  # 4 misses into a 2-slot cache -> 2 evictions
        plan = cache.plan(_fake_key(i), _BACKEND)
        plan.traces += 1
    st = cache.stats()
    assert st["evictions"] == 2
    assert st["plans"] == 2
    # lifetime traces survive eviction: 4 plans traced once each
    assert st["traces"] == 4


def test_plan_cache_counters_flow_into_registry():
    reg = Registry()
    cache = PlanCache(max_plans=2, registry=reg)
    cache.plan(_fake_key(0), _BACKEND)
    cache.plan(_fake_key(0), _BACKEND)
    cache.plan(_fake_key(1), _BACKEND)
    cache.plan(_fake_key(2), _BACKEND)
    assert reg.value("plan_cache.hits") == 1
    assert reg.value("plan_cache.misses") == 3
    assert reg.value("plan_cache.evictions") == 1


def test_stats_to_host_single_device_get(monkeypatch):
    import jax
    import jax.numpy as jnp

    import repro.api.plan as plan_mod
    from repro.core.knn import SearchStats

    stats = SearchStats(
        buckets_visited=jnp.ones((4,), jnp.int32),
        distances=jnp.ones((4,), jnp.int32),
        bound_distances=jnp.ones((4,), jnp.int32),
        padded_distances=jnp.ones((4,), jnp.int32),
        comparisons=jnp.ones((4,), jnp.int32),
        steps=jnp.int32(3),
        topk_inserts=jnp.ones((4,), jnp.int32),
    )
    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(plan_mod.jax, "device_get", counting)
    host = stats_to_host(stats)
    assert len(calls) == 1  # ONE batched fetch, not one per field
    assert set(host) == {"buckets_visited", "distances", "bound_distances",
                         "padded_distances", "comparisons", "steps", "topk_inserts"}
    assert isinstance(host["steps"], int)


# ---------------------------------------------------------------------------
# spans on the profiler's clock, and the device-to-host fetch counters
# ---------------------------------------------------------------------------

SEARCH_SPANS = ("search", "search/plan_lookup", "search/dispatch",
                "search/device_wait", "search/copy_back", "search/record")


def _traced_host_events(tmp_path, fn) -> list[tuple[str, int, int]]:
    """Run ``fn`` under ``jax.profiler.trace`` and return the host plane's
    events as (name, start ns, end ns)."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # annotations only, no per-call events
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        fn()
    (xplane,) = tmp_path.glob("**/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                events += [(e.name, int(e.start_ns),
                            int(e.start_ns + e.duration_ns))
                           for e in line.events]
    return events


def test_search_spans_are_trace_annotations(blob_data, tmp_path):
    idx = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    idx.search(q, k=5)  # compile outside the trace
    events = _traced_host_events(tmp_path, lambda: idx.search(q, k=5))
    found = {n: (a, b) for n, a, b in events if n in SEARCH_SPANS}
    assert set(found) == set(SEARCH_SPANS)
    lo, hi = found["search"]
    for child in SEARCH_SPANS[1:]:
        a, b = found[child]
        assert lo <= a <= b <= hi, child
    # the children follow one another in the order the search runs them
    starts = [found[c][0] for c in SEARCH_SPANS[1:]]
    assert starts == sorted(starts)


def test_search_counts_its_host_fetches(blob_data):
    import jax

    idx = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    d, i, s, isl, router, _ = idx._search_planned(q, k=5)
    assert router is None
    assert len(jax.tree.leaves(s)) == 7 and len(jax.tree.leaves(isl)) == 3
    nbytes = sum(a.nbytes for a in jax.tree.leaves((d, i, s, isl)))
    fetches0 = idx.obs.value("search.host_fetches")
    bytes0 = idx.obs.value("search.host_fetch_bytes")
    idx.search(q, k=5)
    # ONE fetch of every output: dists, ids, SearchStats and IslandStats
    assert idx.obs.value("search.host_fetches") - fetches0 == 1
    assert idx.obs.value("search.host_fetch_bytes") - bytes0 == nbytes
    m = idx.metrics()["search"]
    assert m["host_fetches"] == fetches0 + 1
    assert m["host_fetch_bytes"] == bytes0 + nbytes


def _assert_run_matches_planned(idx, q, k, explain):
    """``_run``'s host values equal a per-leaf ``np.asarray`` of
    ``_search_planned``'s device outputs for the same queries."""
    import jax

    d, i, s, isl, router, plan, *evidence = idx._search_planned(
        q, k=k, explain=explain)
    res, isl_h, router_h, evidence_h = idx._run(q, k, None, None, explain=explain)
    kk = min(k, idx.n_total)
    np.testing.assert_array_equal(res.dists, np.asarray(d)[:, :kk])
    np.testing.assert_array_equal(res.ids, np.asarray(i)[:, :kk])
    assert res.stats.keys() == set(s._fields)
    for name in s._fields:
        np.testing.assert_array_equal(res.stats[name], np.asarray(getattr(s, name)),
                                      err_msg=name)
    if idx.obs.enabled:
        for got, want in zip(jax.tree.leaves(isl_h), jax.tree.leaves(isl), strict=True):
            np.testing.assert_array_equal(got, np.asarray(want))
        assert (router_h is None) == (router is None)
    else:
        assert isl_h is None and router_h is None
    for got, want in zip(jax.tree.leaves(evidence_h), jax.tree.leaves(evidence),
                         strict=True):
        np.testing.assert_array_equal(got, np.asarray(want))
    return res


@pytest.mark.parametrize("enabled", [True, False], ids=["obs-on", "obs-off"])
def test_one_fetch_equals_per_leaf_fetches(blob_data, enabled):
    idx = OverlapIndex.build(blob_data, _cfg(obs=enabled))
    q = np.asarray(blob_data[:8])
    ref = _assert_run_matches_planned(idx, q, 5, explain=False)
    rep = _assert_run_matches_planned(idx, q, 5, explain=True)
    np.testing.assert_array_equal(rep.dists, ref.dists)
    res = idx.search(q, k=5)
    np.testing.assert_array_equal(res.dists, ref.dists)
    np.testing.assert_array_equal(res.ids, ref.ids)
    for name, want in ref.stats.items():
        np.testing.assert_array_equal(res.stats[name], want, err_msg=name)
    # with a delta phase too
    idx.ingest(np.random.default_rng(2).normal(size=(16, blob_data.shape[1]))
               .astype(np.float32))
    _assert_run_matches_planned(idx, q, 5, explain=False)
    _assert_run_matches_planned(idx, q, 5, explain=True)


def test_run_requests_copies_early_and_fetches_once(blob_data, monkeypatch):
    import jax
    from jax._src.array import ArrayImpl

    idx = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    idx.explain(q, k=5)  # compile both plans outside the count
    idx.search(q, k=5)
    log = []
    real_get, real_wait = jax.device_get, jax.block_until_ready
    real_copy = ArrayImpl.copy_to_host_async

    def device_get(x):
        # a get of host values (stats_to_host's, after the fetch) moves nothing
        moved = [a for a in jax.tree.leaves(x) if isinstance(a, jax.Array)]
        if moved:
            log.append(("get", len(moved)))
        return real_get(x)

    def block_until_ready(x):
        log.append(("wait", None))
        return real_wait(x)

    def copy_to_host_async(self):
        log.append(("copy", None))
        return real_copy(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(jax, "block_until_ready", block_until_ready)
    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", copy_to_host_async)
    idx.search(q, k=5)
    # d, i, the 7 SearchStats and 3 IslandStats leaves: each copy requested
    # before the wait, then ONE device_get of all 12 leaves
    n = 2 + 7 + 3
    assert log[:n + 1] == [("copy", None)] * n + [("wait", None)]
    assert [e for e in log if e[0] == "get"] == [("get", n)]
    log.clear()
    idx.explain(q, k=5)
    # explain adds ``order`` and ``visits`` of its VisitRows (no delta
    # phase: ``dorder``/``dvisits`` are None) and ``home``
    assert [e for e in log if e[0] == "get"] == [("get", n + 3)]
    assert log.index(("wait", None)) == n + 3


def test_search_counts_topk_inserts(blob_data):
    """``search.topk_inserts`` adds up each search's per-query count of
    candidates that entered the running top-k; a disabled registry keeps
    nothing, though the result still carries the count."""
    idx = OverlapIndex.build(blob_data, _cfg())
    off = OverlapIndex.build(blob_data, _cfg(obs=False))
    q = np.asarray(blob_data[:8])
    before = idx.obs.value("search.topk_inserts")
    res = idx.search(q, k=5)
    got = res.stats["topk_inserts"]
    assert got.shape == (len(q),) and (got >= 5).all()  # k entries enter a cold top-k
    assert idx.obs.value("search.topk_inserts") - before == int(got.sum())
    assert idx.metrics()["search"]["topk_inserts"] == before + int(got.sum())
    res_off = off.search(q, k=5)
    np.testing.assert_array_equal(res_off.stats["topk_inserts"], got)
    assert off.obs.value("search.topk_inserts") == 0
    assert off.obs.counters() == {}


def test_disabled_obs_adds_no_annotations_or_counters(blob_data, tmp_path):
    idx_off = OverlapIndex.build(blob_data, _cfg(obs=False))
    idx_on = OverlapIndex.build(blob_data, _cfg(obs=True))
    q = np.asarray(blob_data[:8])
    idx_off.search(q, k=5)
    got = {}
    events = _traced_host_events(
        tmp_path, lambda: got.setdefault("off", idx_off.search(q, k=5)))
    names = {n for n, _, _ in events}
    assert not names & set(SEARCH_SPANS)
    assert not any(n.startswith(("search/", "explain/", "ingest/"))
                   for n in names)
    assert idx_off.obs.counters() == {}
    assert idx_off.metrics()["search"]["host_fetches"] == 0
    r_on = idx_on.search(q, k=5)
    assert np.array_equal(got["off"].dists, r_on.dists)
    assert np.array_equal(got["off"].ids, r_on.ids)
    assert got["off"].stats.keys() == r_on.stats.keys()


def test_explain_and_ingest_spans_split_at_the_device(blob_data):
    idx = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    idx.explain(q, k=5)
    g = np.random.default_rng(1)
    idx.ingest(g.normal(size=(16, blob_data.shape[1])).astype(np.float32))
    hists = idx.obs.snapshot()["histograms"]
    for path in ("explain", "explain/plan_lookup", "explain/dispatch",
                 "explain/device_wait", "explain/copy_back",
                 "explain/attribute", "ingest", "ingest/dispatch"):
        assert hists[path]["count"] >= 1, path
    old = ("device_execute", "host_transfer")
    assert not [p for p in hists if p.rsplit("/", 1)[-1] in old]
    # explain's copy-back counts its fetch too: ONE of d, i, stats, rows,
    # home and the island stats
    assert idx.obs.value("search.host_fetches") == 1


# ---------------------------------------------------------------------------
# the build's phases and the forest's shape
# ---------------------------------------------------------------------------

BUILD_SPANS = ("build", "build/dbscan", "build/overlap", "build/decide",
               "build/forest")


def test_build_phases_are_spans_and_the_forest_shape_is_gauged(blob_data, tmp_path):
    events = tmp_path / "build.jsonl"
    q = np.asarray(blob_data[:8])
    got = {}
    host = _traced_host_events(tmp_path / "trace", lambda: got.setdefault(
        "ix", OverlapIndex.build(blob_data, _cfg(events_path=str(events)))))
    idx = got["ix"]
    m = idx.metrics()["build"]
    assert set(m["spans"]) == set(BUILD_SPANS)  # no upload before a search
    assert all(m["spans"][p]["count"] == 1 for p in BUILD_SPANS)
    phases = sum(m["spans"][p]["sum"] for p in BUILD_SPANS[1:])
    assert phases <= m["spans"]["build"]["sum"]
    assert m["indexes"] == idx.n_indexes >= 2
    assert m["overlap_indexes"] == int(idx.forest.is_overlap_index.sum())
    assert m["buckets"] == idx.forest.n_buckets
    # each phase is a profiler annotation nested in ``build``, in order
    found = {n: (a, b) for n, a, b in host if n in BUILD_SPANS}
    assert set(found) == set(BUILD_SPANS)
    lo, hi = found["build"]
    starts = [found[p][0] for p in BUILD_SPANS[1:]]
    assert starts == sorted(starts) and lo <= starts[0]
    assert all(found[p][1] <= hi for p in BUILD_SPANS[1:])
    # the first search uploads the forest, under build/upload, once
    idx.search(q, k=5)
    idx.search(q, k=5)
    upload = idx.metrics()["build"]["spans"]["build/upload"]
    assert upload["count"] == 1
    assert not [p for p in idx.obs.snapshot()["histograms"] if p.endswith("/upload")
                and p != "build/upload"]
    logged = {r["span"] for r in EventLog.read(str(events))}
    assert set(BUILD_SPANS) | {"build/upload"} <= logged


def test_disabled_obs_times_no_build_phase(blob_data, tmp_path):
    got = {}
    host = _traced_host_events(tmp_path, lambda: got.setdefault(
        "ix", OverlapIndex.build(blob_data, _cfg(obs=False))))
    assert not [n for n, _, _ in host if n.startswith("build")]
    m = got["ix"].metrics()["build"]
    assert m == {"spans": {}, "indexes": None, "overlap_indexes": None, "buckets": None,
                 "member_bytes": None, "member_format": None}
