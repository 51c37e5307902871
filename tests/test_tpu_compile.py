"""Compile the main-path Pallas kernels for a TPU v5e chip, at real widths.

Nothing runs: each test lowers one kernel for a described (not attached)
v5e chip and lets the chip's compiler accept or refuse it — the 8x128
tiling rule, gather lowering, operand layouts and SMEM/VMEM limits are all
checked there, none of which interpret mode sees.  Shapes are those of the
DB1 deployment (62,702 x 20, the forest its build produces), the 128-d
embedding shape and the smollm-135m kNN-LM datastore (65,536 x 576).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bucket_scan import SMEM_WORDS, bucket_scan_topk_pallas, prepad_buckets
from repro.kernels.pairwise_l2 import (
    eps_count_pallas,
    eps_min_label_pallas,
    eps_nearest_core_pallas,
    pairwise_sq_l2_int8_pallas,
    pairwise_sq_l2_pallas,
)
from repro.kernels.topk import knn_topk_pallas

DB1_ROWS, DB1_DIM = 62_702, 20
# the forest OverlapIndex.build makes of DB1 (VBM, c_max 250) on the chip
DB1_BUCKETS, DB1_CAPACITY = 519, 250
QUERIES = 100
DBSCAN_BLOCK = 1024  # core/dbscan.py's query block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the kernel itself, not a fallback
    return hlo


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("dim", [DB1_DIM, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8], ids=["f32", "int8"])
def test_bucket_scan_compiles(one_chip, dtype, dim, k):
    """One forest-scan step over the DB1 forest's buckets (beam 1, the
    search default), members prepared once as the search loop does."""
    nb, cap = DB1_BUCKETS, DB1_CAPACITY
    quantized = dtype == jnp.int8

    def step(q, bx, ids, bsel, act, top_d, top_i, *scale):
        bx, ids, sc = prepad_buckets(bx, ids, *scale)
        return bucket_scan_topk_pallas(q, bx, ids, bsel, act, top_d, top_i, sc)

    shapes = [
        ((QUERIES, dim), jnp.float32), ((nb, cap, dim), dtype),
        ((nb, cap), jnp.int32), ((QUERIES, 1), jnp.int32),
        ((QUERIES, 1), jnp.bool_), ((QUERIES, k), jnp.float32),
        ((QUERIES, k), jnp.int32),
    ] + ([((nb, cap), jnp.float32)] if quantized else [])
    _compile(step, *shapes, sharding=one_chip)


@pytest.mark.parametrize("dim", [DB1_DIM, 128])
def test_bucket_scan_compiles_with_every_row_inactive(one_chip, dim):
    """A step in which no row scans its bucket: the merge's loop runs no
    trip.  The activity mask is a constant the compiler sees."""
    nb, cap = DB1_BUCKETS, DB1_CAPACITY

    def step(q, bx, ids, bsel, top_d, top_i):
        bx, ids, _ = prepad_buckets(bx, ids)
        act = jnp.zeros(bsel.shape, jnp.bool_)
        return bucket_scan_topk_pallas(q, bx, ids, bsel, act, top_d, top_i)

    _compile(
        step,
        ((QUERIES, dim), jnp.float32), ((nb, cap, dim), jnp.float32),
        ((nb, cap), jnp.int32), ((QUERIES, 1), jnp.int32),
        ((QUERIES, 10), jnp.float32), ((QUERIES, 10), jnp.int32),
        sharding=one_chip,
    )


@pytest.mark.parametrize(
    "nb,cap,dim,k,own_limit",
    [
        (1_634, 707, 5, 10, False),  # the WARD forest (500,000 x 5, c_max 707)
        (1_785, 632, 128, 10, False),  # the SIFT forest (400,000 x 128, DBM, c_max 632)
        (64, 1_000, 128, 100, True),  # the largest tile the tests build
    ],
    ids=["ward", "sift", "d128-c1000"],
)
def test_bucket_scan_compiles_at_forest_shapes(one_chip, nb, cap, dim, k, own_limit):
    """Each program holds the 8 tiles of its rows, double-buffered; where
    that outgrows the default scoped VMEM the call sets its own limit."""
    from repro.kernels import bucket_scan

    def step(q, bx, ids, bsel, act, top_d, top_i):
        bx, ids, _ = prepad_buckets(bx, ids)
        return bucket_scan_topk_pallas(q, bx, ids, bsel, act, top_d, top_i)

    cp, dp = -(-cap // 128) * 128, -(-dim // 128) * 128
    limit = bucket_scan._vmem_limit(cp, dp, -(-k // 128) * 128, 4, False)
    assert (limit is not None) == own_limit
    _compile(
        step,
        ((QUERIES, dim), jnp.float32), ((nb, cap, dim), jnp.float32),
        ((nb, cap), jnp.int32), ((QUERIES, 1), jnp.int32),
        ((QUERIES, 1), jnp.bool_), ((QUERIES, k), jnp.float32),
        ((QUERIES, k), jnp.int32),
        sharding=one_chip,
    )


def test_bucket_scan_compiles_past_one_smem_load(one_chip):
    """A query batch whose bucket selections exceed one call's SMEM share
    splits into several kernel calls instead of overflowing SMEM."""
    qn, beam = 2 * SMEM_WORDS, 4
    hlo = _compile(
        bucket_scan_topk_pallas,
        ((qn, DB1_DIM), jnp.float32), ((DB1_BUCKETS, DB1_CAPACITY, DB1_DIM), jnp.float32),
        ((DB1_BUCKETS, DB1_CAPACITY), jnp.int32), ((qn, beam), jnp.int32),
        ((qn, beam), jnp.bool_), ((qn, 10), jnp.float32), ((qn, 10), jnp.int32),
        sharding=one_chip,
    )
    assert hlo.count("tpu_custom_call") >= 8


def test_knn_topk_compiles(one_chip):
    """kNN-LM decode retrieval: 4 slots against the 65,536 x 576 datastore."""
    _compile(
        lambda q, x: knn_topk_pallas(q, x, k=8),
        ((4, 576), jnp.float32), ((65_536, 576), jnp.float32),
        sharding=one_chip,
    )


def test_eps_count_compiles(one_chip):
    _compile(
        eps_count_pallas,
        ((DBSCAN_BLOCK, DB1_DIM), jnp.float32), ((DB1_ROWS, DB1_DIM), jnp.float32),
        ((), jnp.float32),
        sharding=one_chip,
    )


def test_eps_min_label_compiles(one_chip):
    _compile(
        eps_min_label_pallas,
        ((DBSCAN_BLOCK, DB1_DIM), jnp.float32), ((DB1_ROWS, DB1_DIM), jnp.float32),
        ((DB1_ROWS,), jnp.int32), ((DB1_ROWS,), jnp.bool_), ((), jnp.float32),
        sharding=one_chip,
    )


def test_eps_nearest_core_compiles(one_chip):
    _compile(
        eps_nearest_core_pallas,
        ((DBSCAN_BLOCK, DB1_DIM), jnp.float32), ((DB1_ROWS, DB1_DIM), jnp.float32),
        ((DB1_ROWS,), jnp.int32), ((DB1_ROWS,), jnp.bool_),
        sharding=one_chip,
    )


def test_pairwise_f32_compiles(one_chip):
    """Bucket lower bounds: queries against the DB1 forest's pivots."""
    _compile(
        pairwise_sq_l2_pallas,
        ((QUERIES, DB1_DIM), jnp.float32), ((DB1_BUCKETS, DB1_DIM), jnp.float32),
        sharding=one_chip,
    )


def test_pairwise_int8_compiles(one_chip):
    """int8 datastore scan at the kNN-LM width."""
    _compile(
        pairwise_sq_l2_int8_pallas,
        ((4, 576), jnp.float32), ((65_536, 576), jnp.int8), ((65_536,), jnp.float32),
        sharding=one_chip,
    )


@pytest.mark.parametrize("layout", ["sharded", "routed"])
def test_layout_search_compiles_for_four_chips(topo, layout, monkeypatch):
    """The DB1 search plan of the sharded and routed layouts over a 2x2
    mesh: every Pallas call must sit inside a shard_map island, since the
    compiler cannot partition one (the routing prefix's kernels did not)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.knn import DeltaView, DeviceForest
    from repro.distributed import knn_island
    from repro.distributed.router.exec import routed_search
    from repro.distributed.router.table import RoutingTable
    from repro.kernels import ops

    # code asks jax.default_backend(), which is the CPU here: steer the
    # dispatch to the compiled kernels, and drop traces of the CPU path
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    mesh = Mesh(np.array(topo.devices).reshape(4), ("model",))
    s, nb, cap, dim, n_idx = 4, 520, DB1_CAPACITY, DB1_DIM, 24

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    rep, row = P(), P("model")
    f32, i32 = jnp.float32, jnp.int32
    forest = DeviceForest(
        sds((n_idx, dim), f32, rep), sds((n_idx,), f32, rep), sds((n_idx, 8), i32, rep),
        sds((nb, cap, dim), f32, row), sds((nb, cap), i32, row),
        sds((nb, cap), jnp.bool_, row), sds((nb, dim), f32, row),
        sds((nb,), f32, row), sds((nb,), i32, row),
    )
    delta = DeltaView(
        sds((n_idx, cap, dim), f32, row), sds((n_idx, cap), i32, row),
        sds((n_idx, cap), jnp.bool_, row), sds((n_idx, dim), f32, row),
        sds((n_idx,), f32, row),
    )
    q = sds((QUERIES, dim), f32, rep)
    if layout == "sharded":
        fn = lambda f, q, d: knn_island.sharded_search(mesh, "model", f, q, d, k=10)
        args = (forest, q, delta)
    else:
        table = RoutingTable(
            sds((s, dim), f32, rep), sds((s,), f32, rep), sds((s,), i32, rep),
            sds((s, n_idx), f32, rep), sds((s, n_idx), i32, rep),
            sds((s, n_idx), i32, rep), sds((s, n_idx), jnp.bool_, rep),
            sds((s, s), f32, rep),
        )
        fn = lambda f, q, d, t: routed_search(mesh, "model", f, q, d, t, k=10)
        args = (forest, q, delta, table)
    try:
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in hlo
