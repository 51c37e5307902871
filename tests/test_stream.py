"""Streaming subsystem tests: ingest routing/append, forest+delta search
exactness, overlap-drift triggers at the ξ threshold, and rebuild hot swaps
(structure freshness + no correctness gap)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Config, IndexConfig, OverlapIndex, StreamConfig
from repro.core import (
    device_forest,
    knn_exact,
    knn_search_impl,
    route_eligibility,
)
from repro.stream import (
    MaintenanceConfig,
    OverlapMonitor,
    alloc_delta,
    delta_view,
    ingest,
    pull_delta_meta,
)

VBM = IndexConfig(method="vbm", eps=1.5, min_pts=8)

# the search executor over a hand-built DeltaView (the facade owns its own)
_search = jax.jit(knn_search_impl, static_argnames=("k", "mode", "beam"))


def _knn(df, q, k, delta=None):
    d, ids, stats, _ = _search(df, jnp.asarray(q), k=k, mode="all", delta=delta)
    return d, ids, stats


def _streaming_index(x, *, capacity, **stream):
    """Build over ``x`` with the drift monitor configured from ``stream``."""
    return OverlapIndex.build(
        x, Config(index=VBM, stream=StreamConfig(capacity=capacity, **stream))
    )


@pytest.fixture(scope="module")
def built(blob_data):
    cfg = IndexConfig(method="vbm", eps=1.5, min_pts=8, xi_min=0.3, xi_max=0.7)
    return blob_data, OverlapIndex.build(blob_data, Config(index=cfg)).forest


def _stream_points(x, n, seed):
    g = np.random.default_rng(seed)
    base = x[g.choice(len(x), n)]
    return (base + 0.3 * g.normal(size=base.shape)).astype(np.float32)


def test_ingest_routes_and_appends(built):
    x, forest = built
    df = device_forest(forest)
    delta = alloc_delta(forest, capacity=64)
    xb = _stream_points(x, 40, seed=0)
    ids = np.arange(len(x), len(x) + 40)
    delta, acc = ingest(df, delta, jnp.asarray(xb), jnp.asarray(ids))
    assert bool(np.asarray(acc).all())
    host = pull_delta_meta(delta, ids=True)
    assert host["count"].sum() == 40
    # routing must equal host-side argmin over index centers
    d = ((xb[:, None, :] - forest.index_centers[None]) ** 2).sum(-1)
    want = d.argmin(axis=1)
    got = np.full(40, -1)
    for i in range(forest.n_indexes):
        for j in range(host["count"][i]):
            got[host["ids"][i, j] - len(x)] = i
    np.testing.assert_array_equal(got, want)
    # every id stored exactly once; coordinates round-trip
    stored = np.sort(host["ids"][host["ids"] >= 0])
    np.testing.assert_array_equal(stored, ids)


def test_ingest_capacity_reject_reported(built):
    x, forest = built
    df = device_forest(forest)
    delta = alloc_delta(forest, capacity=4)
    xb = _stream_points(x, 200, seed=1)
    delta, acc = ingest(
        df, delta, jnp.asarray(xb), jnp.asarray(np.arange(200) + len(x))
    )
    acc = np.asarray(acc)
    host = pull_delta_meta(delta)
    assert (host["count"] <= 4).all()  # never written past capacity
    assert host["count"].sum() == acc.sum()
    assert host["dropped"].sum() == (~acc).sum() > 0  # rejects are visible


def test_forest_plus_delta_matches_brute_force(built, rng):
    x, forest = built
    df = device_forest(forest)
    delta = alloc_delta(forest, capacity=256)
    xs = _stream_points(x, 300, seed=2)
    delta, acc = ingest(
        df, delta, jnp.asarray(xs), jnp.asarray(np.arange(300) + len(x))
    )
    assert bool(np.asarray(acc).all())
    x_all = np.concatenate([x, xs])
    q = rng.normal(size=(24, x.shape[1])).astype(np.float32) * 8
    d, ids, stats = _knn(df, q, 12, delta_view(delta))
    de, _ = knn_exact(jnp.asarray(x_all), jnp.asarray(q), k=12)
    np.testing.assert_allclose(np.asarray(d), np.asarray(de), rtol=1e-4, atol=1e-4)
    # returned ids must cover delta members too (streamed points are findable)
    d2, ids2, _ = _knn(df, xs[:8], 1, delta_view(delta))
    np.testing.assert_array_equal(
        np.asarray(ids2)[:, 0], np.arange(8) + len(x)
    )


def test_empty_delta_is_noop(built, rng):
    x, forest = built
    df = device_forest(forest)
    delta = alloc_delta(forest, capacity=32)
    q = rng.normal(size=(8, x.shape[1])).astype(np.float32) * 8
    d0, i0, _ = _knn(df, q, 7)
    d1, i1, _ = _knn(df, q, 7, delta_view(delta))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_route_eligibility_matches_one_hot_reference(built, rng):
    _, forest = built
    n_idx = forest.n_indexes
    closest = jnp.asarray(rng.integers(0, n_idx, 32), jnp.int32)
    neighbors = jnp.asarray(forest.neighbors)
    sel = np.asarray(route_eligibility(closest, neighbors))
    # reference: dense one-hot construction (the pre-segment-ops semantics)
    want = np.zeros((32, n_idx), bool)
    cl = np.asarray(closest)
    nb = np.asarray(neighbors)
    for qi in range(32):
        want[qi, cl[qi]] = True
        for n in nb[cl[qi]]:
            if n >= 0:
                want[qi, n] = True
    np.testing.assert_array_equal(sel, want)


def test_overlap_drift_trigger_fires_at_xi():
    """The ξ threshold is sharp: rate just above fires, just below doesn't."""
    g = np.random.default_rng(5)
    dim = 6
    c2 = np.zeros(dim)
    c2[0] = 18.0
    x0 = np.concatenate(
        [g.normal(size=(300, dim)), c2 + g.normal(size=(300, dim))]
    ).astype(np.float32)
    sf = _streaming_index(
        x0, capacity=512,
        monitor_method="dbm", xi_rebuild=0.99, fill_rebuild=0.99,
    )
    assert sf.forest.n_indexes == 2
    # corridor points inflate the conservative radii -> DBM rate rises
    mid = np.zeros(dim)
    mid[0] = 9.0
    sf.ingest((mid + g.normal(size=(150, dim)) * [4, 1, 1, 1, 1, 1]).astype(np.float32))
    rep = sf.check()
    worst = float(np.max(rep.rates))
    assert worst > 0.05, "drift scenario must create measurable overlap"
    below = OverlapMonitor(
        sf.forest, MaintenanceConfig(method="dbm", xi_rebuild=worst - 0.02,
                                     fill_rebuild=0.99)
    ).check(sf.delta)
    above = OverlapMonitor(
        sf.forest, MaintenanceConfig(method="dbm", xi_rebuild=worst + 0.02,
                                     fill_rebuild=0.99)
    ).check(sf.delta)
    assert any("overlap" in v for v in below.reasons.values())
    assert not any("overlap" in v for v in above.reasons.values())


@pytest.mark.parametrize("method", ["dbm", "obm"])
def test_monitor_methods_run(built, method):
    x, forest = built
    sf = _streaming_index(
        x, capacity=64,
        monitor_method=method, xi_rebuild=0.9, fill_rebuild=0.9,
    )
    sf.ingest(_stream_points(x, 30, seed=3))
    rep = sf.check()
    assert rep.rates.shape == (sf.forest.n_indexes,) * 2
    assert np.isfinite(rep.rates).all()


def test_rebuild_swap_exactness_and_fresh_structure(built, rng):
    x, forest = built
    sf = _streaming_index(
        x, capacity=128,
        # low fill threshold: rebuilds fire quickly
        monitor_method="dbm", xi_rebuild=0.95, fill_rebuild=0.2,
    )
    stats0 = dict(sf.forest.build_stats)
    for step in range(4):
        sf.ingest(_stream_points(x, 120, seed=10 + step))
        sf.maintain()
    assert sf.forest.build_stats["rebuilds"] > 0
    # counters accumulate across rebuilds (construction-cost metric)
    assert sf.forest.build_stats["tree_distances"] > stats0["tree_distances"]
    # structure rollup reflects the swapped trees (fresh host copies)
    s = sf.structure()
    assert s["total_leaves"] == sf.forest.n_buckets
    assert s["n_objects"] == sf.n_total == len(x) + 4 * 120
    # and search stays exact across all those swaps
    q = rng.normal(size=(16, x.shape[1])).astype(np.float32) * 8
    d, ids, _ = sf.search(q, k=10, mode="all")
    de, _ = knn_exact(jnp.asarray(sf.x_all), jnp.asarray(q), k=10)
    np.testing.assert_allclose(np.asarray(d), np.asarray(de), rtol=1e-4, atol=1e-4)


def test_stale_tree_copies_detected(built):
    """aggregate_structure must refuse to report over stale host trees."""
    x, forest = built
    forest2 = OverlapIndex.build(x[: len(x) // 2], Config(index=VBM)).forest
    broken = type(forest)(
        index_centers=forest.index_centers,
        index_radii=forest.index_radii,
        neighbors=forest.neighbors,
        is_overlap_index=forest.is_overlap_index,
        bucket_x=forest.bucket_x,
        bucket_ids=forest.bucket_ids,
        bucket_mask=forest.bucket_mask,
        bucket_pivot=forest.bucket_pivot,
        bucket_radius=forest.bucket_radius,
        bucket_index=forest.bucket_index,
        c_max=forest.c_max,
        trees=forest2.trees,  # stale/mismatched host copies
        build_stats=forest.build_stats,
    )
    if sum(t.structure.n_leaves for t in forest2.trees) == forest.n_buckets:
        pytest.skip("coincidental leaf-count match")
    with pytest.raises(RuntimeError, match="stale"):
        broken.aggregate_structure()


def test_ingest_never_loses_points_under_overflow(built, rng):
    """Forced maintenance on capacity rejects: every point stays findable."""
    x, forest = built
    sf = _streaming_index(
        x, capacity=16,  # tiny: guaranteed overflow
        monitor_method="dbm", xi_rebuild=0.95, fill_rebuild=0.95,
    )
    xs = _stream_points(x, 400, seed=21)
    ids = sf.ingest(xs)
    # every streamed point must be its own 1-NN through the serving path
    d, got, _ = sf.search(xs[:32], k=1, mode="all")
    np.testing.assert_array_equal(np.asarray(got)[:, 0], ids[:32])
    assert sf.forest.build_stats["rebuilds"] > 0  # overflow forced rebuilds
