"""Tier-2 exactness gates for the sharded device layout (``cfg.layout``).

Every test here asserts BITWISE identity between the single-device
executor and the sharded shard_map islands (distributed/knn_island.py) on
the same data — distances AND ids, f32 and int8, forest and delta phase,
across maintenance rebuild swaps and save/load re-sharding.  Exactness is
the layout layer's contract, not a tolerance: per-member distance
arithmetic is shard-local and identical, and k-per-shard candidates make
the merged global top-k exact.

Run under a forced host mesh (set BEFORE jax initializes):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_sharded_exec.py

On a single-device host the whole module skips (tier-1 collection still
imports it, so an import-time regression fails everywhere).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    Config,
    IndexConfig,
    LayoutConfig,
    ObsConfig,
    OverlapIndex,
    SearchConfig,
    StreamConfig,
    make_backend,
)
from repro.data.synthetic import tracking_like

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="sharded layout tests need >= 4 devices; set "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax init",
)

SHARDED4 = LayoutConfig(kind="sharded", shards=4)


def _tracks() -> np.ndarray:
    """3-d trajectory-like clusters — a second shape/density regime, so the
    bitwise gate is exercised beyond the 8-d blobs fixture."""
    g = np.random.default_rng(21)
    centers = g.normal(size=(6, 3)) * 9.0
    parts = [c + 0.6 * g.normal(size=(300, 3)) for c in centers]
    parts.append(g.uniform(-12, 12, size=(60, 3)))
    return np.concatenate(parts).astype(np.float32)


def _queries(x: np.ndarray, n: int = 24, seed: int = 3) -> np.ndarray:
    g = np.random.default_rng(seed)
    base = x[g.choice(len(x), n)]
    return (base + 0.1 * x.std() * g.normal(size=base.shape)).astype(np.float32)


def _cfg(index_kw: dict, *, quantize=False, capacity=64, layout=None) -> Config:
    return Config(
        index=IndexConfig(**index_kw),
        search=SearchConfig(quantize=quantize),
        stream=StreamConfig(capacity=capacity),
        layout=layout or LayoutConfig(),
    )


@pytest.fixture(scope="module")
def datasets(blob_data):
    return {
        "blobs": (blob_data, dict(method="vbm", eps=1.5, min_pts=8,
                                  xi_min=0.3, xi_max=0.7)),
        "tracks": (_tracks(), dict(method="vbm", eps=0.8, min_pts=8,
                                   xi_min=0.4, xi_max=0.8)),
        # DB1's shape at 6,000 rows: 24 long tracks, so routing often picks
        # an index far from a query's true neighbours and most shards hold
        # none of its selected buckets
        "db1": (tracking_like(6_000), dict(method="vbm", eps=6.0, min_pts=16,
                                           xi_min=0.4, xi_max=0.8)),
    }


@pytest.fixture(scope="module")
def pair(datasets):
    """Factory for a (single-layout, 4-shard) index pair over one dataset.

    ``fresh=True`` returns an uncached pair for tests that MUTATE the
    indexes (ingest / rebuild); read-only tests share the cached builds.
    """
    cache = {}

    def get(name, *, quantize=False, capacity=64, fresh=False):
        key = (name, quantize, capacity)
        if fresh or key not in cache:
            x, kw = datasets[name]
            built = (
                OverlapIndex.build(
                    x, _cfg(kw, quantize=quantize, capacity=capacity)
                ),
                OverlapIndex.build(
                    x, _cfg(kw, quantize=quantize, capacity=capacity,
                            layout=SHARDED4)
                ),
            )
            if fresh:
                return built
            cache[key] = built
        return cache[key]

    return get


def _assert_same_results(res, ref, what=""):
    np.testing.assert_array_equal(res.dists, ref.dists, err_msg=what)
    np.testing.assert_array_equal(res.ids, ref.ids, err_msg=what)
    # eligibility-derived instrumentation must agree too ('visits' may not:
    # each shard's bounded scan terminates on its LOCAL bound ordering)
    np.testing.assert_array_equal(
        res.stats["bound_distances"], ref.stats["bound_distances"], err_msg=what
    )


# ---------------------------------------------------------------------------
# search: forest phase + delta phase, f32 + int8, both datasets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("name", ["blobs", "tracks", "db1"])
def test_search_bitwise_across_layouts(pair, datasets, name, quantize):
    single, sharded = pair(name, quantize=quantize, fresh=True)
    assert sharded.backend.shards == 4
    x, _ = datasets[name]
    q = _queries(x)
    for mode in ("forest", "all"):
        for k in (1, 5, 17):
            _assert_same_results(
                sharded.search(q, k=k, mode=mode),
                single.search(q, k=k, mode=mode),
                what=f"{name}/{mode}/k{k}/no-delta",
            )
    # mid-fill delta: the SAME stream into both layouts, then the two-phase
    # (forest + delta) search must still agree bitwise
    batch = _queries(x, 40, seed=9)
    np.testing.assert_array_equal(single.ingest(batch), sharded.ingest(batch))
    assert int(np.asarray(single.delta.count).sum()) == len(batch)
    for mode in ("forest", "all"):
        _assert_same_results(
            sharded.search(q, k=9, mode=mode),
            single.search(q, k=9, mode=mode),
            what=f"{name}/{mode}/k9/delta",
        )


# ---------------------------------------------------------------------------
# ingest: collective scatter == single-device routing, rejects aggregate
# ---------------------------------------------------------------------------

def test_sharded_ingest_matches_single_with_capacity_rejects(pair, datasets):
    # capacity 16 + batches up to 64: ragged power-of-two padding, chunking,
    # AND the capacity-reject -> forced-rebuild -> retry loop all fire; both
    # layouts must walk the identical deterministic path
    single, sharded = pair("blobs", capacity=16, fresh=True)
    x, _ = datasets["blobs"]
    for seed, n in enumerate((16, 7, 33, 64)):
        batch = _queries(x, n, seed=seed)
        np.testing.assert_array_equal(single.ingest(batch), sharded.ingest(batch))
        for field, a, b in zip(single.delta._fields, single.delta, sharded.delta):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"delta.{field} after n={n}"
            )
    # same compiled-shape discipline on both write paths
    assert single.ingest_stats() == sharded.ingest_stats()
    q = _queries(x)
    _assert_same_results(sharded.search(q, k=8), single.search(q, k=8))


def test_sharded_ingest_never_retraces_steady_state(pair, datasets):
    _, sharded = pair("blobs", fresh=True)
    x, _ = datasets["blobs"]
    for seed, n in enumerate((64, 64, 40, 64)):  # 40 pads up to 64
        sharded.ingest(_queries(x, n, seed=seed))
    st = sharded.ingest_stats()
    assert st["traces"] == 1, f"steady-state sharded ingest re-traced: {st}"
    assert st["calls"] >= 4


# ---------------------------------------------------------------------------
# maintenance: the rebuild hot-swap under sharding
# ---------------------------------------------------------------------------

def test_forced_rebuild_hot_swap_stays_bitwise(pair, datasets):
    single, sharded = pair("blobs", fresh=True)
    x, _ = datasets["blobs"]
    batch = _queries(x, 50, seed=5)
    single.ingest(batch)
    sharded.ingest(batch)
    assert single.forest.n_indexes >= 2
    triggers = [0, single.forest.n_indexes - 1]
    single._rebuild(triggers)
    sharded._rebuild(triggers)
    # survivors kept their buffers, rebuilt indexes absorbed theirs — the
    # LOGICAL delta state must agree exactly post-swap
    assert single.forest.n_indexes == sharded.forest.n_indexes
    np.testing.assert_array_equal(
        np.asarray(single.delta.count), np.asarray(sharded.delta.count)
    )
    q = _queries(x)
    for mode in ("forest", "all"):
        _assert_same_results(
            sharded.search(q, k=7, mode=mode),
            single.search(q, k=7, mode=mode),
            what=f"post-rebuild/{mode}",
        )
    # streaming continues across the swap without divergence
    more = _queries(x, 20, seed=6)
    np.testing.assert_array_equal(single.ingest(more), sharded.ingest(more))
    _assert_same_results(sharded.search(q, k=7), single.search(q, k=7))


# ---------------------------------------------------------------------------
# persistence: snapshots are layout-independent
# ---------------------------------------------------------------------------

def test_persistence_reshard_roundtrip(datasets, tmp_path):
    x, kw = datasets["blobs"]
    ix = OverlapIndex.build(x, _cfg(kw, layout=SHARDED4))
    ix.ingest(_queries(x, 30, seed=4))
    path = ix.save(tmp_path / "sharded.npz")
    q = _queries(x)
    ref = ix.search(q, k=9)

    as_saved = OverlapIndex.load(path)
    as_single = OverlapIndex.load(path, layout=LayoutConfig())
    as_two = OverlapIndex.load(path, layout=LayoutConfig(kind="sharded", shards=2))
    assert as_saved.backend.shards == 4
    assert as_single.backend.kind == "single"
    assert as_two.backend.shards == 2

    for tag, other in (("saved", as_saved), ("single", as_single), ("two", as_two)):
        res = other.search(q, k=9)
        np.testing.assert_array_equal(res.dists, ref.dists, err_msg=tag)
        np.testing.assert_array_equal(res.ids, ref.ids, err_msg=tag)
        # streamed object ids survive the save -> re-shard -> load round trip
        np.testing.assert_array_equal(
            np.asarray(other.delta.ids), np.asarray(ix.delta.ids), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(other.delta.count), np.asarray(ix.delta.count), err_msg=tag
        )


# ---------------------------------------------------------------------------
# serving: the datastore rides the index's layout
# ---------------------------------------------------------------------------

def test_serving_datastore_rides_sharded_layout(pair, datasets):
    from repro.serve.retrieval import forest_knn, ingest_keys

    single, sharded = pair("blobs", fresh=True)
    x, _ = datasets["blobs"]
    vals = np.arange(single.n_total) % 97
    ds_s = single.to_datastore(vals, stream_capacity=128)
    ds_h = sharded.to_datastore(vals, stream_capacity=128)
    assert ds_h.shards == 4

    q = jnp.asarray(_queries(x, 12))
    d_s, v_s = forest_knn(q, ds_s, k=5)
    d_h, v_h = forest_knn(q, ds_h, k=5)
    np.testing.assert_array_equal(np.asarray(d_h), np.asarray(d_s))
    np.testing.assert_array_equal(np.asarray(v_h), np.asarray(v_s))

    # the engine's decode step is the compilation boundary: the island must
    # give the same answers from INSIDE an outer jit
    jit_knn = jax.jit(forest_knn, static_argnames=("k",))
    d_hj, v_hj = jit_knn(q, ds_h, k=5)
    np.testing.assert_array_equal(np.asarray(d_hj), np.asarray(d_s))
    np.testing.assert_array_equal(np.asarray(v_hj), np.asarray(v_s))

    # serve-side streaming: same accepts, same values, same retrievals
    keys = _queries(x, 50, seed=8)
    toks = np.arange(50) % 97
    ds_s2, acc_s = ingest_keys(ds_s, jnp.asarray(keys), toks)
    ds_h2, acc_h = ingest_keys(ds_h, jnp.asarray(keys), toks)
    assert acc_s == acc_h
    assert acc_s > 0
    np.testing.assert_array_equal(
        np.asarray(ds_h2.values), np.asarray(ds_s2.values)
    )
    d_s3, v_s3 = forest_knn(q, ds_s2, k=5)
    d_h3, v_h3 = forest_knn(q, ds_h2, k=5)
    np.testing.assert_array_equal(np.asarray(d_h3), np.asarray(d_s3))
    np.testing.assert_array_equal(np.asarray(v_h3), np.asarray(v_s3))


# ---------------------------------------------------------------------------
# plan + backend plumbing
# ---------------------------------------------------------------------------

def test_plan_keys_distinguish_layouts(pair, datasets):
    single, sharded = pair("blobs")
    x, _ = datasets["blobs"]
    q = _queries(x, 4)
    rs = single.search(q, k=3)
    rh = sharded.search(q, k=3)
    assert rs.plan.key.shards == 1
    assert rh.plan.key.shards == 4
    assert rs.plan.key != rh.plan.key
    assert "shardedx4" in repr(sharded)


def test_layout_default_shards_uses_all_devices():
    backend = make_backend(LayoutConfig(kind="sharded"))
    assert backend.kind == "sharded"
    assert backend.shards == jax.device_count()


# ---------------------------------------------------------------------------
# observability under sharding: metrics gates + per-island attribution
# ---------------------------------------------------------------------------

def _obs_cfg(index_kw: dict, *, enabled=True, layout=None, **obs_kw) -> Config:
    return Config(
        index=IndexConfig(**index_kw),
        search=SearchConfig(),
        stream=StreamConfig(capacity=64),
        layout=layout or SHARDED4,
        obs=ObsConfig(enabled=enabled, **obs_kw),
    )


def test_sharded_metrics_on_off_bitwise(datasets):
    # the no-effect guarantee under the sharded layout: metrics are host-side
    # bookkeeping, so flipping the registry must not move a single bit of
    # the island executors' output — forest phase and delta phase alike
    x, kw = datasets["blobs"]
    on = OverlapIndex.build(x, _obs_cfg(kw))
    off = OverlapIndex.build(x, _obs_cfg(kw, enabled=False))
    batch = _queries(x, 40, seed=9)
    np.testing.assert_array_equal(on.ingest(batch), off.ingest(batch))
    q = _queries(x)
    for mode in ("forest", "all"):
        r_on = on.search(q, k=7, mode=mode)
        r_off = off.search(q, k=7, mode=mode)
        np.testing.assert_array_equal(r_on.dists, r_off.dists, err_msg=mode)
        np.testing.assert_array_equal(r_on.ids, r_off.ids, err_msg=mode)
    assert off.metrics()["enabled"] is False
    assert on.metrics()["search"]["queries"] == 2 * len(q)


def test_sharded_explain_and_tracing_bitwise(datasets, tmp_path):
    from repro.obs import Trace

    x, kw = datasets["blobs"]
    p = str(tmp_path / "trace.jsonl")
    plain = OverlapIndex.build(x, _obs_cfg(kw))
    traced = OverlapIndex.build(
        x, _obs_cfg(kw, trace_sample=1.0, events_path=p)
    )
    batch = _queries(x, 40, seed=9)
    plain.ingest(batch)
    traced.ingest(batch)
    q = _queries(x)
    ref = plain.search(q, k=9)
    r_tr = traced.search(q, k=9)
    np.testing.assert_array_equal(r_tr.dists, ref.dists)
    np.testing.assert_array_equal(r_tr.ids, ref.ids)
    # explain() decodes the sharded VisitRows (shard-local sorted orders +
    # per-phase counts): bitwise results AND exact visit conservation
    rep = traced.explain(q, k=9)
    np.testing.assert_array_equal(rep.result.dists, ref.dists)
    np.testing.assert_array_equal(rep.result.ids, ref.ids)
    np.testing.assert_array_equal(
        rep.contributing + rep.wasted, rep.result.stats["buckets_visited"]
    )
    # the traced search's tree carries one island point event per shard
    tids = Trace.trace_ids(p)
    assert tids
    t = Trace.reconstruct(p, tids[0])
    islands = [r for r in t.records if r.get("event") == "island"]
    assert sorted(r["island"] for r in islands) == [0, 1, 2, 3]


def test_island_counters_sum_to_fleet_totals(datasets):
    x, kw = datasets["blobs"]
    ix = OverlapIndex.build(x, _obs_cfg(kw))
    q = _queries(x)
    ix.search(q, k=5, mode="forest")
    ix.ingest(_queries(x, 40, seed=9))
    # forest mode again: delta-phase work still lands in the island rows,
    # and forest-mode routing keeps the bound_distances relation exact below
    # (mode="all" skips routing entirely)
    ix.search(q, k=9, mode="forest")
    m = ix.metrics()
    assert set(m["islands"]) == {0, 1, 2, 3}
    for name in ("buckets_visited", "distances"):
        fleet = m["search"][name]
        assert fleet > 0
        assert sum(isl[name] for isl in m["islands"].values()) == fleet, name
    # bound_distances: every shard routes the replicated queries itself, so
    # the island rows over-count routing by (S - 1) x queries x centers
    # relative to the fleet total (which counts routing once per query)
    fleet = m["search"]["bound_distances"]
    summed = sum(isl["bound_distances"] for isl in m["islands"].values())
    assert summed == fleet + (4 - 1) * m["search"]["queries"] * ix.n_indexes


def test_sharded_one_fetch_bitwise(datasets):
    # 4 shards: every output of a search or an explain comes back in ONE
    # fetch, bitwise equal to a per-leaf np.asarray of the device outputs
    x, kw = datasets["blobs"]
    ix = OverlapIndex.build(x, _obs_cfg(kw))
    ix.ingest(_queries(x, 40, seed=9))
    q = _queries(x)
    for explain in (False, True):
        d, i, s, isl, _, _, *evidence = ix._search_planned(q, k=7, explain=explain)
        fetches = ix.obs.value("search.host_fetches")
        nbytes = ix.obs.value("search.host_fetch_bytes")
        res, isl_h, _, evidence_h = ix._run(q, 7, None, None, explain=explain)
        assert ix.obs.value("search.host_fetches") - fetches == 1
        assert ix.obs.value("search.host_fetch_bytes") - nbytes == sum(
            a.nbytes for a in jax.tree.leaves((d, i, s, isl, evidence)))
        np.testing.assert_array_equal(res.dists, np.asarray(d))
        np.testing.assert_array_equal(res.ids, np.asarray(i))
        for name in s._fields:
            np.testing.assert_array_equal(
                res.stats[name], np.asarray(getattr(s, name)), err_msg=name)
        for got, want in zip(jax.tree.leaves((isl_h, evidence_h)),
                             jax.tree.leaves((isl, evidence)), strict=True):
            np.testing.assert_array_equal(got, np.asarray(want))
    before = ix.obs.value("search.host_fetches")
    ix.search(q, k=7)
    ix.explain(q, k=7)
    assert ix.obs.value("search.host_fetches") - before == 2
