"""Pallas kernel sweeps: shapes x dtypes, interpret mode vs jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import quantize_datastore
from repro.kernels.pairwise_l2 import (
    pairwise_sq_l2_int8_pallas,
    pairwise_sq_l2_pallas,
)
from repro.kernels.topk import knn_topk_pallas

SHAPES = [
    (8, 16, 4),     # tiny, all-padded
    (64, 64, 64),   # exact tile fit
    (65, 130, 33),  # ragged everything
    (128, 257, 96), # ragged N
    (1, 300, 20),   # single query (decode-style)
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("q_n,x_n,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pairwise_matches_ref(q_n, x_n, d, dtype, rng):
    q = jnp.asarray(rng.normal(size=(q_n, d)), dtype)
    x = jnp.asarray(rng.normal(size=(x_n, d)), dtype)
    got = pairwise_sq_l2_pallas(q, x, bq=64, bn=64, bd=64, interpret=True)
    want = ref.pairwise_sq_l2_ref(q, x)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


@pytest.mark.parametrize("bq,bn,bd", [(16, 16, 16), (64, 32, 128)])
def test_pairwise_block_shape_invariance(bq, bn, bd, rng):
    q = jnp.asarray(rng.normal(size=(70, 40)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(90, 40)), jnp.float32)
    got = pairwise_sq_l2_pallas(q, x, bq=bq, bn=bn, bd=bd, interpret=True)
    want = ref.pairwise_sq_l2_ref(q, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("q_n,x_n,d", [(16, 100, 24), (33, 257, 48)])
@pytest.mark.parametrize("k", [1, 5, 16])
def test_knn_topk_matches_ref(q_n, x_n, d, k, rng):
    q = jnp.asarray(rng.normal(size=(q_n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(x_n, d)), jnp.float32)
    gv, gi = knn_topk_pallas(q, x, k=k, bq=16, bn=64, interpret=True)
    wv, wi = ref.knn_topk_ref(q, x, k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(wv), rtol=1e-4, atol=1e-4)
    # indices must point at rows achieving those distances (ties allowed)
    d2 = np.asarray(ref.pairwise_sq_l2_ref(q, x))
    picked = d2[np.arange(q_n)[:, None], np.asarray(gi)]
    np.testing.assert_allclose(picked, np.asarray(gv), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("k", [1, 10, 100])
def test_knn_topk_ties_go_to_smaller_index(k, rng):
    # small integers: every distance is exact and most of them tie, within
    # an N-tile and across tiles, so the merge's tie rule decides the answer
    q = jnp.asarray(rng.integers(0, 3, size=(20, 6)), jnp.float32)
    x = jnp.asarray(rng.integers(0, 3, size=(230, 6)), jnp.float32)
    gv, gi = knn_topk_pallas(q, x, k=k, bq=16, bn=64, interpret=True)
    d2 = ref.pairwise_sq_l2_ref(q, x)
    ids = jnp.broadcast_to(jnp.arange(x.shape[0], dtype=jnp.int32), d2.shape)
    wv, wi = ref.topk_by_distance_then_id(d2, ids, k)
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))


def test_knn_topk_fewer_rows_than_k(rng):
    q = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    gv, gi = knn_topk_pallas(q, x, k=8, bq=16, bn=16, interpret=True)
    assert np.isinf(np.asarray(gv)[:, 3:]).all()
    assert (np.asarray(gi)[:, 3:] == -1).all()


@pytest.mark.parametrize("q_n,x_n,d", [(16, 64, 32), (40, 130, 20)])
def test_pairwise_int8_matches_ref(q_n, x_n, d, rng):
    q = jnp.asarray(rng.normal(size=(q_n, d)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(x_n, d)), jnp.float32)
    xq, scale = quantize_datastore(x)
    got = pairwise_sq_l2_int8_pallas(q, xq, scale, bq=32, bn=32, bd=32, interpret=True)
    want = ref.pairwise_sq_l2_int8_ref(q, xq, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    # quantization error vs exact distances stays small for unit-scale data
    exact = ref.pairwise_sq_l2_ref(q, x)
    rel = np.abs(np.asarray(got) - np.asarray(exact)) / (np.asarray(exact) + 1.0)
    assert rel.mean() < 0.05


def test_ops_dispatch_cpu_uses_ref(rng):
    """On CPU without force-pallas, ops must route to the oracle (fast path)."""
    from repro.kernels import ops

    q = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(9, 8)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.pairwise_sq_l2(q, x)),
        np.asarray(ref.pairwise_sq_l2_ref(q, x)),
        rtol=1e-6,
    )
