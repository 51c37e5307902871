"""OverlapIndex facade tests: config-tree validation, overlap-method
registry, plan-cache re-trace behavior, save/load bitwise round-trip
(including snapshots that carry a retired config key), the baseline
pivot-method contract, and the whole lifecycle end to end."""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    Config,
    ConfigError,
    IndexConfig,
    LayoutConfig,
    OverlapIndex,
    SearchConfig,
    StreamConfig,
    available_overlap_methods,
    make_backend,
    register_overlap_method,
    unregister_overlap_method,
)
from repro.core import knn_exact, knn_search_impl
from repro.core.overlap import overlap_matrix
from repro.core.pipeline import build_baseline_core

CFG = Config(
    index=IndexConfig(method="vbm", eps=1.5, min_pts=8, xi_min=0.3, xi_max=0.7),
    stream=StreamConfig(capacity=128),
)


@pytest.fixture(scope="module")
def built(blob_data):
    return OverlapIndex.build(blob_data, CFG)


def _stream_points(x, n, seed):
    g = np.random.default_rng(seed)
    base = x[g.choice(len(x), n)]
    return (base + 0.3 * g.normal(size=base.shape)).astype(np.float32)


# ---------------------------------------------------------------------------
# config tree validation
# ---------------------------------------------------------------------------

BAD_CONFIGS = [
    (lambda: IndexConfig(method="vbmm"), "registered overlap method"),
    (lambda: IndexConfig(xi_min=0.8, xi_max=0.4), "xi_min < xi_max"),
    (lambda: IndexConfig(xi_min=-0.1), "xi_min < xi_max"),
    (lambda: IndexConfig(xi_max=1.5), "xi_min < xi_max"),
    (lambda: IndexConfig(eps=0.0), "eps"),
    (lambda: IndexConfig(min_pts=0), "min_pts"),
    (lambda: IndexConfig(c_max=1), "c_max"),
    (lambda: IndexConfig(pivot_method="median"), "pivot_method"),
    (lambda: IndexConfig(dbscan_block=0), "dbscan_block"),
    (lambda: SearchConfig(k=0), "k="),
    (lambda: SearchConfig(mode="fast"), "mode"),
    (lambda: SearchConfig(beam=0), "beam"),
    (lambda: StreamConfig(capacity=0), "capacity"),
    (lambda: StreamConfig(monitor_method="learned"), "registered overlap method"),
    (lambda: StreamConfig(xi_rebuild=0.0), "xi_rebuild"),
    (lambda: StreamConfig(xi_rebuild=1.5), "xi_rebuild"),
    (lambda: StreamConfig(drift_margin=-0.1), "drift_margin"),
    (lambda: StreamConfig(fill_rebuild=0.0), "fill_rebuild"),
    (lambda: StreamConfig(pivot_method="median"), "pivot_method"),
    (lambda: StreamConfig(c_max=1), "c_max"),
    (lambda: LayoutConfig(kind="mirrored"), "LayoutConfig.kind"),
    (lambda: LayoutConfig(kind="sharded", shards=0), "shards"),
    (lambda: LayoutConfig(shards=2), "kind='sharded'"),
    (lambda: LayoutConfig(axis=""), "axis"),
]


@pytest.mark.parametrize("bad, fragment", BAD_CONFIGS,
                         ids=[f[1] + str(i) for i, f in enumerate(BAD_CONFIGS)])
def test_config_validation_is_actionable(bad, fragment):
    with pytest.raises(ConfigError) as exc:
        bad()
    assert fragment in str(exc.value)


def test_config_nodes_are_type_checked():
    with pytest.raises(ConfigError, match="Config.index"):
        Config(index=SearchConfig())


def test_config_valid_tree_constructs():
    cfg = Config(
        index=IndexConfig(method="obm", c_max=None),
        search=SearchConfig(k=3, mode="all", beam=4),
        stream=StreamConfig(capacity=None, drift_margin=0.1),
    )
    assert cfg.with_(eps=2.0).index.eps == 2.0


# ---------------------------------------------------------------------------
# overlap-method registry
# ---------------------------------------------------------------------------

def test_registry_lists_paper_methods():
    assert set(available_overlap_methods()) >= {"vbm", "dbm", "obm"}


def test_registered_method_flows_through_whole_pipeline(blob_data):
    """A custom heuristic becomes buildable + monitorable by NAME — no
    dispatch site anywhere needs touching."""

    def hybrid(pivots, radii, *, x=None, assign=None):
        v = overlap_matrix("vbm", pivots, radii)
        d = overlap_matrix("dbm", pivots, radii)
        return 0.5 * (v + d)

    register_overlap_method("hybrid-vd", hybrid)
    try:
        cfg = Config(
            index=IndexConfig(method="hybrid-vd", eps=1.5, min_pts=8),
            stream=StreamConfig(monitor_method="hybrid-vd", capacity=64),
        )
        ix = OverlapIndex.build(blob_data, cfg)
        assert ix.forest.n_indexes >= 1
        ix.ingest(_stream_points(blob_data, 16, seed=0))
        rep = ix.check()  # the monitor resolves the same registry entry
        assert np.isfinite(rep.rates).all()
    finally:
        unregister_overlap_method("hybrid-vd")
    with pytest.raises(ConfigError, match="hybrid-vd"):
        IndexConfig(method="hybrid-vd")


def test_registry_duplicate_and_unknown():
    with pytest.raises(ValueError, match="already registered"):
        register_overlap_method("vbm", lambda *a, **k: None)
    with pytest.raises(ValueError, match="registered methods"):
        overlap_matrix("nope", jnp.zeros((2, 3)), jnp.ones((2,)))


# ---------------------------------------------------------------------------
# plan cache: no re-trace on stable shapes
# ---------------------------------------------------------------------------

def test_search_plan_cache_never_retraces_stable_shapes(built, rng):
    ix = built
    q = rng.normal(size=(16, 8)).astype(np.float32) * 8
    r1 = ix.search(q, k=9)
    plan = r1.plan
    assert plan.traces == 1 and len(ix.plans) >= 1
    for _ in range(3):
        r = ix.search(q, k=9)
    assert r.plan is plan
    assert plan.traces == 1, "same options + same shapes must not re-trace"
    assert plan.calls >= 4
    assert ix.plans.hits >= 3

    # a different option tuple is a DIFFERENT plan, original stays warm
    r2 = ix.search(q, k=5, mode="all")
    assert r2.plan is not plan and r2.plan.traces == 1
    assert plan.traces == 1

    # a new batch shape re-specializes within the plan (counted, cached)
    ix.search(q[:7], k=9)
    assert plan.traces == 2
    ix.search(q[:7], k=9)
    assert plan.traces == 2


def test_plan_cache_lru_evicts_and_counts():
    """The cache is bounded: exceeding max_plans drops the least-recently-
    USED plan (a later re-request simply recompiles as a fresh miss)."""
    from repro.api.plan import PlanCache, PlanKey

    def key(k):
        return PlanKey(k=k, mode="forest", beam=1,
                       quantize=False, delta_capacity=None)

    backend = make_backend(LayoutConfig())
    cache = PlanCache(max_plans=2)
    cache.plan(key(1), backend)
    cache.plan(key(2), backend)
    cache.plan(key(1), backend)  # refresh recency: key(2) is now the LRU entry
    cache.plan(key(3), backend)  # over the cap -> evicts key(2)
    assert key(2) not in cache and key(1) in cache and key(3) in cache
    st = cache.stats()
    assert (st["plans"], st["max_plans"]) == (2, 2)
    assert (st["hits"], st["misses"], st["evictions"]) == (1, 3, 1)
    cache.plan(key(2), backend)  # re-request: a plain recompile, not an error
    assert cache.stats()["misses"] == 4 and cache.stats()["evictions"] == 2
    assert len(cache) == 2
    with pytest.raises(ValueError, match="max_plans"):
        PlanCache(max_plans=0)


def test_ingest_executor_never_retraces_ragged_batches(blob_data):
    """Steady-state streaming compiles ONE ingest program: ragged tail
    chunks pad up to a power-of-two shape (rows parked invalid), so only a
    genuinely new padded shape re-traces."""
    ix = OverlapIndex.build(blob_data, CFG)  # capacity=128
    ix.ingest(_stream_points(blob_data, 64, seed=0))
    assert ix.ingest_stats()["traces"] == 1
    ix.ingest(_stream_points(blob_data, 64, seed=1))
    ix.ingest(_stream_points(blob_data, 40, seed=2))  # pads up to 64
    st = ix.ingest_stats()
    assert st["traces"] == 1, f"steady-state ingest re-traced: {st}"
    assert st["calls"] >= 3
    ix.ingest(_stream_points(blob_data, 17, seed=3))  # pads to 32: new shape
    assert ix.ingest_stats()["traces"] == 2


def test_make_backend_strict_raises_clamp_downgrades():
    """An explicit build with more shards than devices fails with the XLA
    override hint; the load path clamps (with a warning) so a snapshot from
    a bigger host still opens here."""
    import jax

    too_many = jax.device_count() + 1
    layout = LayoutConfig(kind="sharded", shards=too_many)
    with pytest.raises(ConfigError, match="xla_force_host_platform_device_count"):
        make_backend(layout)
    with pytest.warns(UserWarning, match="re-sharding"):
        backend = make_backend(layout, clamp=True)
    assert backend.shards == jax.device_count()


def test_search_overrides_are_validated(built, rng):
    """Per-call k/mode/beam get the same actionable errors as the config
    tree — and a bad combination never poisons the plan cache."""
    q = rng.normal(size=(4, 8)).astype(np.float32)
    n_plans = len(built.plans)
    with pytest.raises(ConfigError, match="k=0"):
        built.search(q, k=0)
    with pytest.raises(ConfigError, match="beam=0"):
        built.search(q, k=3, beam=0)
    with pytest.raises(ConfigError, match="mode"):
        built.search(q, k=3, mode="fast")
    assert len(built.plans) == n_plans


def test_search_result_matches_legacy_tuple(built, rng):
    """SearchResult (facade) must agree with the bare executor's tuple."""
    from repro.stream import delta_view

    ix = built
    q = rng.normal(size=(8, 8)).astype(np.float32) * 8
    res = ix.search(q, k=7, mode="all")
    run = jax.jit(knn_search_impl, static_argnames=("k", "mode", "beam"))
    delta = None if ix.device_delta is None else delta_view(ix.device_delta)
    d, i, s, _ = run(ix.device, jnp.asarray(q), k=7, mode="all", delta=delta)
    np.testing.assert_array_equal(res.dists, np.asarray(d))
    np.testing.assert_array_equal(res.ids, np.asarray(i))
    assert res.stats["steps"] == int(s.steps)
    d2, i2, s2 = res  # tuple-unpacking compatibility
    assert d2 is res.dists and i2 is res.ids


# ---------------------------------------------------------------------------
# persistence: build -> ingest -> save -> load is bitwise-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True])
def test_save_load_roundtrip_bitwise(blob_data, rng, tmp_path, quantize):
    cfg = Config(
        index=IndexConfig(method="vbm", eps=1.5, min_pts=8,
                          xi_min=0.3, xi_max=0.7),
        search=SearchConfig(quantize=quantize),
        stream=StreamConfig(capacity=128),
    )
    ix = OverlapIndex.build(blob_data, cfg)
    ix.ingest(_stream_points(blob_data, 200, seed=3))  # live delta buffers
    q = rng.normal(size=(24, 8)).astype(np.float32) * 8

    path = ix.save(tmp_path / f"index_q{int(quantize)}")
    ix2 = OverlapIndex.load(path)

    assert ix2.cfg == ix.cfg
    assert ix2.n_total == ix.n_total
    np.testing.assert_array_equal(
        np.asarray(ix2.delta.ids), np.asarray(ix.delta.ids)
    )
    # the drift monitor's baseline is the SAVED one, not a recompute over
    # the restart-time dataset (object-based triggers must not shift)
    np.testing.assert_array_equal(
        ix2.monitor.rates_baseline, ix.monitor.rates_baseline
    )
    np.testing.assert_array_equal(
        np.asarray(ix2.device.bucket_x), np.asarray(ix.device.bucket_x)
    )
    for k, mode in ((12, "all"), (5, "forest")):
        a = ix.search(q, k=k, mode=mode)
        b = ix2.search(q, k=k, mode=mode)
        np.testing.assert_array_equal(a.dists, b.dists)
        np.testing.assert_array_equal(a.ids, b.ids)
        for field in ("buckets_visited", "distances", "comparisons"):
            np.testing.assert_array_equal(a.stats[field], b.stats[field])

    # the loaded index is fully alive: ingest + maintain + structure work
    ix2.ingest(_stream_points(blob_data, 32, seed=4))
    ix2.maintain()
    s = ix2.structure()
    assert s["n_objects"] == ix2.n_total == len(blob_data) + 232
    # and exactness holds over everything ever ingested (int8 bucket
    # storage is deliberately approximate: ~0.5% distance error)
    tol = 1e-2 if quantize else 1e-4
    d = ix2.search(q, k=10, mode="all").dists
    de, _ = knn_exact(jnp.asarray(ix2.x_all), jnp.asarray(q), k=10)
    np.testing.assert_allclose(d, np.asarray(de), rtol=tol, atol=tol)


def test_load_refuses_newer_format(built, tmp_path):
    from repro.api import persist

    path = built.save(tmp_path / "v.npz")
    with np.load(path, allow_pickle=False) as z:
        payload = dict(z)
    payload["format_version"] = np.int64(persist.FORMAT_VERSION + 1)
    np.savez(path, **payload)
    with pytest.raises(ValueError, match="newer format"):
        OverlapIndex.load(path)


def _rewrite_search_config(path, **extra):
    """Add ``extra`` keys to a saved snapshot's search config, in place."""
    with np.load(path, allow_pickle=False) as z:
        payload = dict(z)
    cfg = json.loads(str(payload["config_json"]))
    cfg["search"].update(extra)
    payload["config_json"] = np.array(json.dumps(cfg))
    with open(path, "wb") as f:
        np.savez_compressed(f, **payload)


def test_load_drops_retired_kernel_key(built, rng, tmp_path):
    """Snapshots saved while ``SearchConfig`` had a ``kernel`` field carry
    ``"kernel": true``: load drops that retired key and nothing else."""
    path = built.save(tmp_path / "old")
    _rewrite_search_config(path, kernel=True)
    ix2 = OverlapIndex.load(path)
    assert ix2.cfg == built.cfg
    q = rng.normal(size=(12, 8)).astype(np.float32) * 8
    for k, mode in ((6, "all"), (4, "forest")):
        a = built.search(q, k=k, mode=mode)
        b = ix2.search(q, k=k, mode=mode)
        np.testing.assert_array_equal(a.dists, b.dists)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(
            a.stats["buckets_visited"], b.stats["buckets_visited"]
        )
    # any other unknown key is still an error
    _rewrite_search_config(path, kernal=True)
    with pytest.raises(TypeError, match="kernal"):
        OverlapIndex.load(path)


# ---------------------------------------------------------------------------
# baseline pivot-method contract (was: silently hardcoded 'kmeans')
# ---------------------------------------------------------------------------

def test_baseline_honors_pivot_method_and_warns():
    x = np.random.default_rng(0).normal(size=(300, 5)).astype(np.float32)
    # no config -> the documented 2-means baseline, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        f_km, rep_km = build_baseline_core(x, None)
    assert rep_km.config.pivot_method == "kmeans"
    # explicit non-kmeans config is honored (cheaper GH build) + warned
    with pytest.warns(UserWarning, match="BCCF baseline"):
        f_gh, rep_gh = build_baseline_core(x, IndexConfig(pivot_method="gh"))
    assert rep_gh.config.pivot_method == "gh"
    assert rep_gh.tree_distances < rep_km.tree_distances, (
        "gh pivots must actually be used (2-means costs strictly more)"
    )
    # explicit kmeans: honored, silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        build_baseline_core(x, IndexConfig(pivot_method="kmeans"))


# ---------------------------------------------------------------------------
# the whole lifecycle through the facade
# ---------------------------------------------------------------------------

def test_facade_lifecycle_runs_end_to_end(blob_data, tmp_path):
    """The whole facade surface — build, search, ingest, maintain, save,
    load, to_datastore, the serve-side read and write paths, baseline —
    runs in one sequence."""
    ix = OverlapIndex.build(blob_data, CFG)
    ix.search(blob_data[:4], k=3)
    ix.ingest(_stream_points(blob_data, 150, seed=5))
    ix.search(blob_data[:4], k=3, mode="all")
    ix.maintain()
    path = ix.save(tmp_path / "clean")
    ix2 = OverlapIndex.load(path)
    ix2.search(blob_data[:4], k=3)
    ds = ix2.to_datastore(
        np.arange(ix2.n_total, dtype=np.int32) % 50, stream_capacity=16
    )
    # serve-side read+write paths too
    from repro.serve.retrieval import forest_knn, ingest_keys

    d2, vals = forest_knn(jnp.asarray(blob_data[:4]), ds, 3)
    assert vals.shape == (4, 3)
    ds, acc = ingest_keys(
        ds, jnp.asarray(_stream_points(blob_data, 4, seed=6)),
        jnp.arange(4, dtype=jnp.int32),
    )
    assert acc > 0
    baseline = OverlapIndex.baseline(blob_data[:300])
    baseline.search(blob_data[:4], k=3, mode="all")


# ---------------------------------------------------------------------------
# to_datastore contract
# ---------------------------------------------------------------------------

def test_to_datastore_checks_value_count(built):
    with pytest.raises(ValueError, match="one value per indexed object"):
        built.to_datastore(np.zeros(3, np.int32))


def test_to_datastore_carries_live_delta(blob_data):
    ix = OverlapIndex.build(blob_data, CFG)
    xs = _stream_points(blob_data, 8, seed=7)
    ids = ix.ingest(xs)
    vals = (np.arange(ix.n_total) % 97).astype(np.int32)
    ds = ix.to_datastore(vals)
    from repro.serve.retrieval import forest_knn

    _, got = forest_knn(jnp.asarray(xs), ds, 1)
    np.testing.assert_array_equal(np.asarray(got)[:, 0], vals[ids])
