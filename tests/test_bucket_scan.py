"""Fused bucket-scan kernel (kernels/bucket_scan.py) vs its jnp oracle.

Interpret-mode sweeps on CPU (the REPRO_FORCE_PALLAS=1 path), covering the
forest-scan edge cases: fewer than k reachable objects, duplicate
distances, D not a multiple of the tile width, beam not dividing NB — plus
the end-to-end exactness guarantee that the kernelized ``mode='all'``
search still matches brute force.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import IndexConfig, build_baseline, knn_exact, knn_search_host
from repro.core.knn import device_forest, knn_search
from repro.kernels import ref
from repro.kernels.bucket_scan import bucket_scan_topk_pallas
from repro.kernels.ops import quantize_datastore


def _problem(rng, qn, nb, cap, dim, beam, kk, *, pad_frac=0.3, seeded_topk=True):
    q = jnp.asarray(rng.normal(size=(qn, dim)), jnp.float32)
    bx = jnp.asarray(rng.normal(size=(nb, cap, dim)), jnp.float32)
    ids = jnp.asarray(
        np.arange(nb * cap, dtype=np.int32).reshape(nb, cap)
    )
    ids = jnp.where(jnp.asarray(rng.random((nb, cap)) < pad_frac), -1, ids)
    bsel = jnp.asarray(rng.integers(0, nb, size=(qn, beam)), jnp.int32)
    act = jnp.asarray(rng.random((qn, beam)) < 0.75)
    if qn >= 8:
        # the first 8-row block (one kernel program) holds every case: rows
        # 0-3 read one bucket, row 4 is inactive, rows 5-7 read their own
        bsel = bsel.at[1:4].set(bsel[0])
        act = act.at[0:4].set(True).at[4].set(False)
    if seeded_topk:
        top_d = jnp.sort(
            jnp.asarray(rng.random((qn, kk)).astype(np.float32) * 40.0), axis=1
        )
        top_d = top_d.at[:, kk // 2 :].set(jnp.inf)
        top_i = jnp.where(
            jnp.isinf(top_d), -1,
            jnp.asarray(rng.integers(10_000, 20_000, (qn, kk)), jnp.int32),
        )
    else:
        top_d = jnp.full((qn, kk), jnp.inf)
        top_i = jnp.full((qn, kk), -1, jnp.int32)
    return q, bx, ids, bsel, act, top_d, top_i


def _check_ids_achieve_values(q, bx, ids, got_d, got_i):
    """Returned ids must achieve the returned distances (tie-tolerant)."""
    flat_x = np.asarray(bx).reshape(-1, bx.shape[-1])
    flat_ids = np.asarray(ids).reshape(-1)
    qn = q.shape[0]
    got_d = np.asarray(got_d)
    got_i = np.asarray(got_i)
    for qi in range(qn):
        for j in range(got_d.shape[1]):
            gid = got_i[qi, j]
            if gid < 0 or gid >= 10_000 or not np.isfinite(got_d[qi, j]):
                continue  # seeded/pad entries carry no coordinates
            rows = flat_x[flat_ids == gid]
            d2 = ((rows - np.asarray(q)[qi]) ** 2).sum(-1)
            assert np.any(np.abs(d2 - got_d[qi, j]) < 1e-3), (qi, j, gid)


SHAPES = [
    # (Q, NB, C, D, beam, kk) — D=6/33 exercise the lane-padding path,
    # C=5 the sublane padding, kk=7/11 the alignment tail
    (4, 7, 5, 6, 3, 4),
    (2, 9, 8, 16, 4, 7),
    (1, 3, 2, 33, 2, 5),
    (5, 6, 4, 8, 6, 11),
    # several 8-row blocks, the last one partial, at beam 1 and 3 and
    # k 10 and 100 (see _problem for the first block's selections)
    (19, 12, 10, 20, 1, 10),
    (19, 7, 9, 5, 3, 100),
    (100, 24, 12, 20, 1, 10),
    (100, 16, 16, 6, 3, 100),
    # D=128: lane-aligned, nothing padded (the SIFT shape)
    (19, 10, 24, 128, 2, 10),
]


@pytest.mark.parametrize("qn,nb,cap,dim,beam,kk", SHAPES)
def test_bucket_scan_matches_ref(qn, nb, cap, dim, beam, kk, rng):
    q, bx, ids, bsel, act, top_d, top_i = _problem(rng, qn, nb, cap, dim, beam, kk)
    rd, ri = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    _check_ids_achieve_values(q, bx, ids, kd, ki)
    # result stays sorted ascending (inf tail allowed; inf-inf diffs are nan)
    with np.errstate(invalid="ignore"):
        diffs = np.diff(np.asarray(kd), axis=1)
    assert np.all((diffs >= -1e-6) | np.isnan(diffs))


def test_bucket_scan_splits_query_batches_past_smem(rng, monkeypatch):
    """A batch whose (Q * beam) bucket selections exceed one call's SMEM
    share runs as several row chunks, with the unsplit answer."""
    from repro.kernels import bucket_scan

    qn, nb, cap, dim, beam, kk = 37, 6, 5, 7, 3, 6  # a shape no other test traces
    q, bx, ids, bsel, act, top_d, top_i = _problem(rng, qn, nb, cap, dim, beam, kk)
    monkeypatch.setattr(bucket_scan, "SMEM_WORDS", 16)  # 8 rows per call
    kd, ki = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    rd, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    _check_ids_achieve_values(q, bx, ids, kd, ki)


def test_bucket_scan_fewer_than_k_reachable(rng):
    """Heavily padded buckets + sparse activity: inf/-1 tail, no garbage."""
    q, bx, ids, bsel, act, top_d, top_i = _problem(
        rng, 3, 4, 3, 5, 2, 9, pad_frac=0.8, seeded_topk=False
    )
    rd, ri = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.isinf(np.asarray(kd)), np.asarray(ki) == -1)


def test_bucket_scan_dry_pool_keeps_ids_unique(rng):
    """Partially filled top-k + a step contributing NO live candidates: the
    kernel's min-extraction must not re-emit an already-extracted id once
    the pool runs dry (regression: argmin over an all-inf row points at an
    arbitrary slot)."""
    qn, nb, cap, dim, beam, kk = 2, 3, 4, 5, 2, 5
    q = jnp.asarray(rng.normal(size=(qn, dim)), jnp.float32)
    bx = jnp.asarray(rng.normal(size=(nb, cap, dim)), jnp.float32)
    ids = jnp.full((nb, cap), -1, jnp.int32)  # every member is padding
    bsel = jnp.asarray(rng.integers(0, nb, size=(qn, beam)), jnp.int32)
    act = jnp.zeros((qn, beam), bool)  # ...and nothing is active anyway
    top_d = jnp.array([[1.0, 2.5, jnp.inf, jnp.inf, jnp.inf]] * qn, jnp.float32)
    top_i = jnp.array([[42, 7, -1, -1, -1]] * qn, jnp.int32)
    rd, ri = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    assert np.array_equal(np.asarray(ki), np.asarray(top_i))  # unchanged


def test_bucket_scan_duplicate_distances(rng):
    """Exactly tied candidates: equal distances go to the smaller id, in
    the kernel and in the oracle alike, whatever the bucket visit order —
    what keeps answers identical across device layouts."""
    qn, nb, cap, dim, beam, kk = 3, 5, 4, 6, 3, 10
    q = jnp.asarray(rng.normal(size=(qn, dim)), jnp.float32)
    # duplicate the same member row across buckets -> equal distances
    row = rng.normal(size=(dim,)).astype(np.float32)
    bx = np.broadcast_to(row, (nb, cap, dim)).copy()
    bx[2:] = rng.normal(size=(nb - 2, cap, dim))
    bx = jnp.asarray(bx, jnp.float32)
    ids = jnp.asarray(np.arange(nb * cap, dtype=np.int32).reshape(nb, cap))
    # visit the duplicate buckets in DESCENDING id order: position order
    # would then put the larger ids first
    bsel = jnp.asarray(np.tile([1, 0, 3], (qn, 1)), jnp.int32)
    act = jnp.ones((qn, beam), bool)
    top_d = jnp.full((qn, kk), jnp.inf)
    top_i = jnp.full((qn, kk), -1, jnp.int32)
    rd, ri = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    _check_ids_achieve_values(q, bx, ids, kd, ki)
    kd, ki, ri = np.asarray(kd), np.asarray(ki), np.asarray(ri)
    np.testing.assert_array_equal(ki, ri)
    tied = np.diff(kd, axis=1) == 0
    assert tied.any()  # the shape must exercise the rule
    assert (np.diff(ki, axis=1)[tied] > 0).all()


def test_bucket_scan_int8_matches_ref(rng):
    qn, nb, cap, dim, beam, kk = 4, 6, 5, 12, 3, 6
    q, bx, ids, bsel, act, top_d, top_i = _problem(rng, qn, nb, cap, dim, beam, kk)
    xq, scale = quantize_datastore(bx.reshape(nb * cap, dim))
    bxq = xq.reshape(nb, cap, dim)
    bscale = scale.reshape(nb, cap)
    rd, _ = ref.bucket_scan_topk_ref(q, bxq, ids, bsel, act, top_d, top_i, bscale)
    kd, _ = bucket_scan_topk_pallas(
        q, bxq, ids, bsel, act, top_d, top_i, bscale, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-4, atol=1e-4)


def test_bucket_scan_int8_blocks_match_ref(rng):
    """int8 members over several query blocks, shared and inactive rows."""
    qn, nb, cap, dim, beam, kk = 19, 9, 12, 20, 3, 10
    q, bx, ids, bsel, act, top_d, top_i = _problem(rng, qn, nb, cap, dim, beam, kk)
    xq, scale = quantize_datastore(bx.reshape(nb * cap, dim))
    bxq = xq.reshape(nb, cap, dim)
    bscale = scale.reshape(nb, cap)
    rd, ri = ref.bucket_scan_topk_ref(q, bxq, ids, bsel, act, top_d, top_i, bscale)
    kd, ki = bucket_scan_topk_pallas(
        q, bxq, ids, bsel, act, top_d, top_i, bscale, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ki)[4], np.asarray(top_i)[4])  # inactive


@pytest.fixture()
def small_forest():
    g = np.random.default_rng(3)
    x = g.normal(size=(90, 5)).astype(np.float32) * 4
    forest, _ = build_baseline(x, IndexConfig(c_max=8))
    return x, forest


def _forced_pallas(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    # drop traces cached before the env flip (dispatch reads env at trace time)
    knn_search.clear_cache()


@pytest.mark.parametrize("beam", [4, 7])
def test_search_beam_not_dividing_nb(small_forest, monkeypatch, beam):
    """Forced-pallas search with beam not dividing NB == jnp-reference search."""
    x, forest = small_forest
    assert forest.n_buckets % beam != 0, "shape must exercise the pad lanes"
    g = np.random.default_rng(5)
    q = g.normal(size=(4, 5)).astype(np.float32) * 4
    d_ref, _, s_ref = knn_search_host(forest, q, k=6, mode="all", beam=beam, kernel=False)
    _forced_pallas(monkeypatch)
    try:
        d_k, _, s_k = knn_search_host(forest, q, k=6, mode="all", beam=beam, kernel=True)
    finally:
        knn_search.clear_cache()
    np.testing.assert_allclose(d_k, d_ref, rtol=1e-4, atol=1e-4)
    assert np.array_equal(s_k["buckets_visited"], s_ref["buckets_visited"])
    assert np.array_equal(s_k["distances"], s_ref["distances"])


def test_kernelized_mode_all_exact(small_forest, monkeypatch):
    """Acceptance: kernelized mode='all' still matches brute force."""
    x, forest = small_forest
    g = np.random.default_rng(11)
    q = g.normal(size=(6, 5)).astype(np.float32) * 4
    de, _ = knn_exact(jnp.asarray(x), jnp.asarray(q), k=10)
    _forced_pallas(monkeypatch)
    try:
        d, ids, _ = knn_search_host(forest, q, k=10, mode="all", kernel=True)
    finally:
        knn_search.clear_cache()
    np.testing.assert_allclose(d, np.asarray(de), rtol=1e-4, atol=1e-4)
    assert (np.asarray(ids) >= 0).all()


def test_quantized_bucket_storage_recall(small_forest):
    """int8 bucket storage (device_forest knob): near-exact neighbors."""
    x, forest = small_forest
    g = np.random.default_rng(13)
    q = g.normal(size=(8, 5)).astype(np.float32) * 4
    de, ie = knn_exact(jnp.asarray(x), jnp.asarray(q), k=5)
    df = device_forest(forest, quantize=True)
    assert df.bucket_x.dtype == jnp.int8 and df.bucket_scale is not None
    d, ids, _ = knn_search_host(forest, q, k=5, mode="all", quantize=True)
    ie = np.asarray(ie)
    recall = np.mean(
        [len(set(ids[i].tolist()) & set(ie[i].tolist())) / 5 for i in range(len(q))]
    )
    assert recall >= 0.9, recall
    np.testing.assert_allclose(d, np.asarray(de), rtol=0.05, atol=0.05)
