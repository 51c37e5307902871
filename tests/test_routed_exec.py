"""Tier-2 exactness gates for the routed device layout (distributed/router/).

The routing tier's contract is the sharded layout's contract plus one more
theorem: host pruning must be INVISIBLE in the results.  Every test here
asserts BITWISE identity (distances AND ids) between the routed executor —
under every fanout mode — the plain sharded fan-all islands, and the
single-device executor, across f32/int8, the delta phase, maintenance
rebuild swaps, and save -> re-route -> load; plus a direct soundness check
of the pruning rule itself (a pruned host's nearest owned member always
sits strictly beyond the fan-all kth-best).

Run under a forced host mesh (set BEFORE jax initializes):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest tests/test_routed_exec.py

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
    RoutingConfig,
    SearchConfig,
    StreamConfig,
    make_backend,
)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="routed layout tests need >= 4 devices; set "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax init",
)

ROUTED4 = LayoutConfig(kind="routed", shards=4)
SHARDED4 = LayoutConfig(kind="sharded", shards=4)
INDEX_KW = dict(method="vbm", eps=2.5, min_pts=8, xi_min=0.3, xi_max=0.7)


def _islands(seed: int = 0, n_per: int = 400, spread: float = 30.0) -> np.ndarray:
    """Well-separated clusters — the workload the routing tier exists for:
    most hosts provably cannot hold a near-cluster query's answer.  The
    spread keeps inter-cluster gaps >> cluster radii (strong pruning) while
    the int8 quantization grid (~spread/40 per step) stays fine enough that
    distinct members keep distinct quantized distances — exact ties would
    merge in layout-dependent order on ANY multi-host layout, fan-all
    included."""
    g = np.random.default_rng(seed)
    centers = g.normal(size=(4, 8)) * spread
    return np.concatenate(
        [c + g.normal(size=(n_per, 8)) for c in centers]
    ).astype(np.float32)


def _queries(x: np.ndarray, n: int = 24, seed: int = 3) -> np.ndarray:
    g = np.random.default_rng(seed)
    base = x[g.choice(len(x), n)]
    return (base + 0.05 * g.normal(size=base.shape)).astype(np.float32)


def _cfg(*, quantize=False, capacity=64, layout=None, index_kw=None) -> Config:
    return Config(
        index=IndexConfig(**(index_kw or INDEX_KW)),
        search=SearchConfig(quantize=quantize),
        stream=StreamConfig(capacity=capacity),
        layout=layout or LayoutConfig(),
        obs=ObsConfig(enabled=True),
    )


def _assert_same_results(res, ref, what=""):
    np.testing.assert_array_equal(res.dists, ref.dists, err_msg=what)
    np.testing.assert_array_equal(res.ids, ref.ids, err_msg=what)


@pytest.fixture(scope="module")
def data():
    return _islands()


@pytest.fixture(scope="module")
def trio(data):
    """Factory: (single, sharded fan-all, routed) triple over the same data.
    ``fresh=True`` for tests that mutate (ingest/rebuild)."""
    cache = {}

    def get(*, quantize=False, routing=None, fresh=False):
        key = (quantize, routing)
        if fresh or key not in cache:
            routed = LayoutConfig(
                kind="routed", shards=4, routing=routing or RoutingConfig()
            )
            built = tuple(
                OverlapIndex.build(data, _cfg(quantize=quantize, layout=lay))
                for lay in (LayoutConfig(), SHARDED4, routed)
            )
            if fresh:
                return built
            cache[key] = built
        return cache[key]

    return get


# ---------------------------------------------------------------------------
# bitwise identity: routed == fan-all == single, every fanout mode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantize", [False, True], ids=["f32", "int8"])
def test_routed_bitwise_across_layouts(trio, data, quantize):
    single, sharded, routed = trio(quantize=quantize, fresh=True)
    assert routed.backend.kind == "routed" and routed.backend.shards == 4
    q = _queries(data)
    for mode in ("forest", "all"):
        for k in (1, 5, 17):
            ref = single.search(q, k=k, mode=mode)
            _assert_same_results(
                sharded.search(q, k=k, mode=mode), ref,
                what=f"sharded/{mode}/k{k}",
            )
            _assert_same_results(
                routed.search(q, k=k, mode=mode), ref,
                what=f"routed/{mode}/k{k}",
            )
    # the delta phase folds into the eligibility bounds: same stream into
    # all three layouts, still bitwise
    batch = _queries(data, 40, seed=9)
    np.testing.assert_array_equal(single.ingest(batch), routed.ingest(batch))
    sharded.ingest(batch)
    for mode in ("forest", "all"):
        ref = single.search(q, k=9, mode=mode)
        _assert_same_results(
            routed.search(q, k=9, mode=mode), ref, what=f"delta/{mode}"
        )


@pytest.mark.parametrize("fanout", ["targeted", "all"])
def test_forced_fanout_modes_stay_bitwise(trio, data, fanout):
    single, _, routed = trio(routing=RoutingConfig(fanout=fanout))
    q = _queries(data)
    for k in (1, 7):
        _assert_same_results(
            routed.search(q, k=k), single.search(q, k=k),
            what=f"fanout={fanout}/k{k}",
        )
    m = routed.metrics()["router"]
    assert m["fanout"][fanout] > 0
    other = "all" if fanout == "targeted" else "targeted"
    assert m["fanout"][other] == 0
    if fanout == "targeted":
        assert m["pruned_hosts"] > 0  # clustered data: pruning actually fires


# ---------------------------------------------------------------------------
# cost model + metrics: targeted on clustered, fan-all on uniform
# ---------------------------------------------------------------------------

def test_auto_picks_targeted_on_clustered_and_reports(trio, data):
    single, _, routed = trio(fresh=True)
    q = _queries(data)
    ref = single.search(q, k=10)
    _assert_same_results(routed.search(q, k=10), ref, what="auto")
    m = routed.metrics()["router"]
    assert m["queries"] == len(q)
    # clustered + well-separated: the lower bounds prune most of the fleet
    assert m["eligible_hosts"] < 4 * len(q)
    assert m["fanout"]["targeted"] == len(q) and m["fanout"]["all"] == 0
    assert m["pruned_hosts"] > 0
    assert 0 < m["est_bytes"]["targeted"] < m["est_bytes"]["all"]
    assert m["table"]["hosts"] == 4
    assert sum(m["table"]["host_counts"]) == routed.n_total


def test_auto_degenerates_to_fanall_on_uniform():
    g = np.random.default_rng(5)
    x = g.uniform(-10, 10, size=(1200, 6)).astype(np.float32)
    kw = dict(method="vbm", eps=1.8, min_pts=6, xi_min=0.3, xi_max=0.7)
    single = OverlapIndex.build(x, _cfg(index_kw=kw))
    routed = OverlapIndex.build(x, _cfg(index_kw=kw, layout=ROUTED4))
    q = _queries(x, 16, seed=2)
    _assert_same_results(routed.search(q, k=10), single.search(q, k=10))
    m = routed.metrics()["router"]
    # nothing prunable -> pricing must refuse the routing-tier overhead
    assert m["fanout"]["all"] == len(q) and m["fanout"]["targeted"] == 0
    assert m["pruned_hosts"] == 0


# ---------------------------------------------------------------------------
# pruning soundness: the rule itself, not just its end-to-end shadow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_pruning_soundness_property(seed):
    """For every (query, pruned host): the host's nearest owned member lies
    STRICTLY beyond the fan-all kth-best, so dropping the host cannot touch
    the top-k.  Checked against brute-force numpy distances with the
    ownership arithmetic the executor actually shards by."""
    from repro.core import knn as cknn
    from repro.core.metric import pairwise
    from repro.distributed.router import host_eligibility
    from repro.distributed.router.table import shard_owners

    x = _islands(seed=seed, n_per=250, spread=60.0 * (1 + seed))
    ix = OverlapIndex.build(x, _cfg(layout=ROUTED4))
    q = _queries(x, 16, seed=seed + 7)
    k = 8
    res = ix.search(q, k=k)

    dev = ix.device
    table = ix.backend.table
    d_center = jnp.sqrt(cknn.route_points(dev.index_centers, jnp.asarray(q))[0])
    sel, _, _ = cknn.route_select(dev, jnp.asarray(q), mode="forest")
    d_host = pairwise(jnp.asarray(q), table.host_centers, metric="l2",
                      use_kernel=False)
    elig, _ = host_eligibility(table, d_center, d_host, sel, k)
    elig = np.asarray(elig)

    # brute-force per-host nearest member under the executor's ownership
    f = ix.forest
    owner = shard_owners(f.n_buckets, 4)  # (NB,)
    mask = np.asarray(f.bucket_mask)
    member_owner = np.broadcast_to(owner[:, None], mask.shape)[mask]  # (N,)
    member_x = np.asarray(f.bucket_x, np.float32)[mask]  # (N, D)
    d = np.sqrt(((q[:, None, :] - member_x[None]) ** 2).sum(-1))  # (Q, N)
    kth = np.sqrt(np.asarray(res.dists)[:, -1])  # searches return squared
    for h in range(4):
        on_h = member_owner == h
        if not on_h.any():
            continue
        nearest = d[:, on_h].min(axis=1)
        dropped = ~elig[:, h]
        assert (nearest[dropped] > kth[dropped]).all(), f"host {h} unsound"
    # and the property is not vacuous: something was actually pruned
    assert (~elig).any()


# ---------------------------------------------------------------------------
# maintenance: rebuild swaps refresh the table
# ---------------------------------------------------------------------------

def test_rebuild_swap_refreshes_table_and_stays_bitwise(trio, data):
    single, _, routed = trio(fresh=True)
    batch = _queries(data, 50, seed=5)
    single.ingest(batch)
    routed.ingest(batch)
    before = np.asarray(jax.device_get(routed.backend.table.count_hi))
    assert single.forest.n_indexes >= 2
    triggers = [0, single.forest.n_indexes - 1]
    single._rebuild(triggers)
    routed._rebuild(triggers)
    after = np.asarray(jax.device_get(routed.backend.table.count_hi))
    # absorbed delta members moved into the tree: ownership counts moved too
    # (the table counts FOREST members; survivors' un-absorbed buffers stay
    # in the delta term of the eligibility bounds, not in the table)
    assert after.sum() > before.sum()
    assert after.sum() == np.asarray(routed.forest.bucket_mask).sum()
    q = _queries(data)
    for mode in ("forest", "all"):
        _assert_same_results(
            routed.search(q, k=7, mode=mode),
            single.search(q, k=7, mode=mode),
            what=f"post-rebuild/{mode}",
        )


# ---------------------------------------------------------------------------
# persistence: RoutingConfig round trip + host-count clamp rebuilds the table
# ---------------------------------------------------------------------------

def test_persistence_reroute_roundtrip(data, tmp_path):
    routing = RoutingConfig(fanout="targeted", overlap_method="dbm")
    ix = OverlapIndex.build(
        data,
        _cfg(layout=LayoutConfig(kind="routed", shards=4, routing=routing)),
    )
    ix.ingest(_queries(data, 30, seed=4))
    path = ix.save(tmp_path / "routed.npz")
    q = _queries(data)
    ref = ix.search(q, k=9)

    as_saved = OverlapIndex.load(path)
    assert as_saved.backend.kind == "routed"
    assert as_saved.cfg.layout.routing == routing  # config round-trips typed
    assert as_saved.backend.routing.fanout == "targeted"
    _assert_same_results(as_saved.search(q, k=9), ref, what="saved")
    tab = as_saved.backend.table
    # forest members only: the streamed-but-unabsorbed rows ride the delta
    assert int(jax.device_get(tab.host_counts).sum()) == int(
        np.asarray(as_saved.forest.bucket_mask).sum()
    )

    # layout override at load: routed -> single and routed -> sharded
    as_single = OverlapIndex.load(path, layout=LayoutConfig())
    as_sharded = OverlapIndex.load(path, layout=SHARDED4)
    _assert_same_results(as_single.search(q, k=9), ref, what="to-single")
    _assert_same_results(as_sharded.search(q, k=9), ref, what="to-sharded")


def test_load_clamp_rebuilds_routing_table(data, tmp_path, monkeypatch):
    """A snapshot saved routed x4 loaded on a 2-device host must re-shard
    AND rebuild the table for the clamped ownership — a 4-host table over
    2-host islands would silently mis-route."""
    ix = OverlapIndex.build(data, _cfg(layout=ROUTED4))
    path = ix.save(tmp_path / "clamp.npz")
    q = _queries(data)
    ref = ix.search(q, k=9)

    real_count = jax.device_count
    monkeypatch.setattr(jax, "device_count", lambda *a, **kw: 2)
    try:
        with pytest.warns(UserWarning, match="re-sharding to 2"):
            clamped = OverlapIndex.load(path)
        assert clamped.backend.kind == "routed"
        assert clamped.backend.shards == 2
        res = clamped.search(q, k=9)
    finally:
        monkeypatch.setattr(jax, "device_count", real_count)
    _assert_same_results(res, ref, what="clamped")
    tab = jax.device_get(clamped.backend.table)
    assert tab.host_counts.shape == (2,)  # table rebuilt for 2 hosts
    assert int(tab.host_counts.sum()) == ix.n_total
    assert clamped.metrics()["router"]["table"]["hosts"] == 2


# ---------------------------------------------------------------------------
# serving: the datastore rides the routed layout
# ---------------------------------------------------------------------------

def test_serving_datastore_rides_routed_layout(trio, data):
    from repro.serve.retrieval import forest_knn

    single, _, routed = trio()
    vals = np.arange(single.n_total) % 97
    ds_s = single.to_datastore(vals, stream_capacity=128)
    ds_r = routed.to_datastore(vals, stream_capacity=128)
    assert ds_r.shards == 4
    assert ds_r.router_table is not None
    assert ds_r.fanout == "auto"

    q = jnp.asarray(_queries(data, 12))
    d_s, v_s = forest_knn(q, ds_s, k=5)
    d_r, v_r = forest_knn(q, ds_r, k=5)
    np.testing.assert_array_equal(np.asarray(d_r), np.asarray(d_s))
    np.testing.assert_array_equal(np.asarray(v_r), np.asarray(v_s))

    # inside an outer jit — the engine's decode step boundary
    jit_knn = jax.jit(forest_knn, static_argnames=("k",))
    d_rj, v_rj = jit_knn(q, ds_r, k=5)
    np.testing.assert_array_equal(np.asarray(d_rj), np.asarray(d_s))
    np.testing.assert_array_equal(np.asarray(v_rj), np.asarray(v_s))


# ---------------------------------------------------------------------------
# plumbing: plan keys, explain, defaults
# ---------------------------------------------------------------------------

def test_plan_keys_carry_fanout(trio, data):
    _, sharded, routed = trio()
    q = _queries(data, 4)
    rr = routed.search(q, k=3)
    rs = sharded.search(q, k=3)
    assert rr.plan.key.fanout == "auto"
    assert rs.plan.key.fanout is None
    assert rr.plan.key != rs.plan.key
    assert "routed" in repr(routed)


def test_routed_explain_bitwise_with_router_stats(trio, data):
    single, _, routed = trio()
    q = _queries(data, 8)
    ref = single.search(q, k=5)
    rep = routed.explain(q, k=5)
    np.testing.assert_array_equal(rep.result.dists, ref.dists)
    np.testing.assert_array_equal(rep.result.ids, ref.ids)
    np.testing.assert_array_equal(
        rep.contributing + rep.wasted, rep.result.stats["buckets_visited"]
    )


def test_routed_single_shard_degenerates():
    backend = make_backend(LayoutConfig(kind="routed", shards=1))
    assert backend.kind == "single"  # one host: routing is vacuous
