"""Fused bucket-scan kernel (kernels/bucket_scan.py) vs its jnp oracle.

Interpret-mode sweeps on CPU (the REPRO_FORCE_PALLAS=1 path), covering the
forest-scan edge cases: fewer than k reachable objects, duplicate
distances, D not a multiple of the tile width, beam not dividing NB — plus
the end-to-end exactness guarantee that the kernelized ``mode='all'``
search still matches brute force.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Config, IndexConfig, OverlapIndex, SearchConfig
from repro.core import knn_exact
from repro.kernels import ref
from repro.kernels.bucket_scan import bucket_scan_topk_pallas
from repro.kernels.ops import quantize_datastore
from repro.kernels.topk import insert_topk


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
    rd, ri, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki, _ = bucket_scan_topk_pallas(
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
    kd, ki, _ = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    rd, _, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    _check_ids_achieve_values(q, bx, ids, kd, ki)


def test_bucket_scan_fewer_than_k_reachable(rng):
    """Heavily padded buckets + sparse activity: inf/-1 tail, no garbage."""
    q, bx, ids, bsel, act, top_d, top_i = _problem(
        rng, 3, 4, 3, 5, 2, 9, pad_frac=0.8, seeded_topk=False
    )
    rd, ri, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki, _ = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.isinf(np.asarray(kd)), np.asarray(ki) == -1)


def test_bucket_scan_dry_pool_keeps_ids_unique(rng):
    """Partially filled top-k + a step contributing NO live candidates: the
    kernel's merge must not re-emit an id already in the top-k once the
    pool runs dry (regression: argmin over an all-inf row points at an
    arbitrary slot)."""
    qn, nb, cap, dim, beam, kk = 2, 3, 4, 5, 2, 5
    q = jnp.asarray(rng.normal(size=(qn, dim)), jnp.float32)
    bx = jnp.asarray(rng.normal(size=(nb, cap, dim)), jnp.float32)
    ids = jnp.full((nb, cap), -1, jnp.int32)  # every member is padding
    bsel = jnp.asarray(rng.integers(0, nb, size=(qn, beam)), jnp.int32)
    act = jnp.zeros((qn, beam), bool)  # ...and nothing is active anyway
    top_d = jnp.array([[1.0, 2.5, jnp.inf, jnp.inf, jnp.inf]] * qn, jnp.float32)
    top_i = jnp.array([[42, 7, -1, -1, -1]] * qn, jnp.int32)
    rd, ri, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki, _ = bucket_scan_topk_pallas(
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
    rd, ri, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki, _ = bucket_scan_topk_pallas(
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
    rd, _, _ = ref.bucket_scan_topk_ref(q, bxq, ids, bsel, act, top_d, top_i, bscale)
    kd, _, _ = bucket_scan_topk_pallas(
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
    rd, ri, _ = ref.bucket_scan_topk_ref(q, bxq, ids, bsel, act, top_d, top_i, bscale)
    kd, ki, _ = bucket_scan_topk_pallas(
        q, bxq, ids, bsel, act, top_d, top_i, bscale, interpret=True
    )
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(ki)[4], np.asarray(top_i)[4])  # inactive


MERGE_CASES = ["ties", "duplicate_ids", "first_fill", "inactive_block", "dead_rows"]


def _int_problem(rng, case, kk):
    """Integer coordinates, so every squared distance is exact in f32 and the
    kernel's distances are the oracle's bit for bit: what is left to differ
    is the merge.  19 rows (three 8-row blocks, the last partial), C = 13
    (not a multiple of 8), beam 2; the carry is a sorted top-k of the oracle.
    """
    qn, nb, cap, dim, beam = 19, 9, 13, 3, 2
    span = 1 if case == "ties" else 4  # coordinates in [-1, 1]: many ties
    q = jnp.asarray(rng.integers(-span, span + 1, (qn, dim)), jnp.float32)
    bx = jnp.asarray(rng.integers(-span, span + 1, (nb, cap, dim)), jnp.float32)
    ids = np.arange(nb * cap, dtype=np.int32).reshape(nb, cap)
    ids[rng.random((nb, cap)) < 0.2] = -1
    if case == "dead_rows":
        ids[0] = -1  # rows that select bucket 0 have no live candidate
    ids = jnp.asarray(ids)
    bsel = jnp.asarray(rng.integers(0, nb, (qn, beam)), jnp.int32)
    act = np.ones((qn, beam), bool)
    if case == "inactive_block":
        act[8:16] = False  # the second block: no row scans anything
    elif case == "dead_rows":
        bsel = bsel.at[::3].set(0)
    act = jnp.asarray(act)
    empty_d, empty_i = jnp.full((qn, kk), jnp.inf), jnp.full((qn, kk), -1, jnp.int32)
    if case == "first_fill":
        return q, bx, ids, bsel, act, empty_d, empty_i
    if case == "duplicate_ids":
        # the carry already holds this very step's candidates: every one of
        # them arrives again with the same (distance, id)
        top_d, top_i, _ = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, empty_d, empty_i)
        return q, bx, ids, bsel, act, top_d, top_i
    n = max(1, kk // 2)  # a half-full carry of integer distances
    top_d, top_i, _ = ref.merge_counting(
        empty_d, empty_i,
        jnp.asarray(rng.integers(0, 8 * span * span * dim, (qn, n)), jnp.float32),
        jnp.asarray(rng.integers(0, nb * cap, (qn, n)), jnp.int32),
    )
    return q, bx, ids, bsel, act, top_d, top_i


@pytest.mark.parametrize("kk", [1, 10, 100])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_insertion_merge_matches_ref_bitwise(case, kk, rng):
    """The gated insertion merge keeps the oracle's (distance, id) top-k bit
    for bit, duplicates included, and counts the same insertions per row."""
    q, bx, ids, bsel, act, top_d, top_i = _int_problem(rng, case, kk)
    rd, ri, rn = ref.bucket_scan_topk_ref(q, bx, ids, bsel, act, top_d, top_i)
    kd, ki, kn = bucket_scan_topk_pallas(
        q, bx, ids, bsel, act, top_d, top_i, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kn), np.asarray(rn))
    kd, ki, kn = np.asarray(kd), np.asarray(ki), np.asarray(kn)
    assert np.array_equal(np.isinf(kd), ki == -1)
    if case == "inactive_block":
        assert (kn[8:16] == 0).all()
        np.testing.assert_array_equal(ki[8:16], np.asarray(top_i)[8:16])
    if case == "dead_rows":
        dead = np.asarray(act).all(1) & (np.asarray(bsel) == 0).all(1)
        assert dead.any() and (kn[dead] == 0).all()
    if case == "first_fill":
        # a cold top-k ends holding min(live, k) candidates, each inserted
        # once; a later bucket may push out what an earlier one put in
        live = (np.asarray(ids)[np.asarray(bsel)] >= 0).sum((1, 2))
        assert (kn >= np.minimum(live, kk)).all() and (kn > 0).all()
    if case == "duplicate_ids":
        # a candidate equal to an entry below the k-th enters beside it; one
        # equal to the k-th itself does not
        if kk == 1:
            assert kn.sum() == 0
        else:
            assert any(len(set(r[r >= 0])) < (r >= 0).sum() for r in ki)


def _merge_kernel(tv_ref, ti_ref, cv_ref, ci_ref, ov_ref, oi_ref, on_ref, *, kk):
    ov_ref[...], oi_ref[...], n = insert_topk(
        tv_ref[...], ti_ref[...], cv_ref[...], ci_ref[...], kk
    )
    on_ref[...] = jnp.broadcast_to(n, on_ref.shape)


@pytest.mark.parametrize("kk", [1, 10, 100])
def test_insert_topk_keeps_lanes_past_k_empty(kk, rng):
    """Across the carry's whole lane width: lanes below k are the oracle's
    top-k of [carry | candidates], lanes from k on stay (inf, -1)."""
    from jax.experimental import pallas as pl

    rows, width, w = 8, 128, 40
    cand_d = jnp.asarray(rng.integers(0, 30, (rows, w)), jnp.float32)
    cand_i = jnp.asarray(rng.integers(0, 50, (rows, w)), jnp.int32)
    cand_d = jnp.where(cand_i < 5, jnp.inf, cand_d)  # masked candidates
    cand_i = jnp.where(cand_i < 5, -1, cand_i)
    seed_d = jnp.asarray(rng.integers(0, 30, (rows, kk)), jnp.float32)
    seed_i = jnp.asarray(rng.integers(0, 50, (rows, kk)), jnp.int32)
    empty_d, empty_i = jnp.full((rows, kk), jnp.inf), jnp.full((rows, kk), -1, jnp.int32)
    top_d, top_i, _ = ref.merge_counting(empty_d, empty_i, seed_d[:, : kk // 2], seed_i[:, : kk // 2])
    want_d, want_i, want_n = ref.merge_counting(top_d, top_i, cand_d, cand_i)
    pad = ((0, 0), (0, width - kk))
    ov, oi, on = pl.pallas_call(
        functools.partial(_merge_kernel, kk=kk),
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.float32),
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.int32),
        ],
        interpret=True,
    )(jnp.pad(top_d, pad, constant_values=jnp.inf), jnp.pad(top_i, pad, constant_values=-1),
      cand_d, cand_i)
    ov, oi = np.asarray(ov), np.asarray(oi)
    np.testing.assert_array_equal(ov[:, :kk], np.asarray(want_d))
    np.testing.assert_array_equal(oi[:, :kk], np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(on)[:, 0], np.asarray(want_n))
    assert np.isinf(ov[:, kk:]).all() and (oi[:, kk:] == -1).all()


SMALL = IndexConfig(c_max=8)


def _small_index(x, **search):
    """A fresh index, hence a fresh plan cache: its first search traces the
    executor anew, and the kernels/ops dispatch reads the environment then."""
    return OverlapIndex.baseline(x, Config(index=SMALL, search=SearchConfig(**search)))


@pytest.fixture()
def small_forest():
    g = np.random.default_rng(3)
    x = g.normal(size=(90, 5)).astype(np.float32) * 4
    return x, _small_index(x)


@pytest.mark.parametrize("beam", [4, 7])
def test_search_beam_not_dividing_nb(small_forest, monkeypatch, beam):
    """Forced-pallas search with beam not dividing NB == jnp-reference search."""
    x, ix_ref = small_forest
    assert ix_ref.forest.n_buckets % beam != 0, "shape must exercise the pad lanes"
    g = np.random.default_rng(5)
    q = g.normal(size=(4, 5)).astype(np.float32) * 4
    d_ref, _, s_ref = ix_ref.search(q, k=6, mode="all", beam=beam)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    ix_k = _small_index(x)
    np.testing.assert_array_equal(ix_k.forest.bucket_ids, ix_ref.forest.bucket_ids)
    d_k, _, s_k = ix_k.search(q, k=6, mode="all", beam=beam)
    np.testing.assert_allclose(d_k, d_ref, rtol=1e-4, atol=1e-4)
    assert np.array_equal(s_k["buckets_visited"], s_ref["buckets_visited"])
    assert np.array_equal(s_k["distances"], s_ref["distances"])
    assert np.array_equal(s_k["topk_inserts"], s_ref["topk_inserts"])


def test_kernelized_mode_all_exact(small_forest, monkeypatch):
    """Acceptance: kernelized mode='all' still matches brute force."""
    x, _ = small_forest
    g = np.random.default_rng(11)
    q = g.normal(size=(6, 5)).astype(np.float32) * 4
    de, _ = knn_exact(jnp.asarray(x), jnp.asarray(q), k=10)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    d, ids, _ = _small_index(x).search(q, k=10, mode="all")
    np.testing.assert_allclose(d, np.asarray(de), rtol=1e-4, atol=1e-4)
    assert (np.asarray(ids) >= 0).all()


def test_quantized_bucket_storage_recall(small_forest):
    """int8 bucket storage (SearchConfig.quantize): near-exact neighbors."""
    x, _ = small_forest
    g = np.random.default_rng(13)
    q = g.normal(size=(8, 5)).astype(np.float32) * 4
    de, ie = knn_exact(jnp.asarray(x), jnp.asarray(q), k=5)
    ix = _small_index(x, quantize=True)
    assert ix.device.bucket_x.dtype == jnp.int8 and ix.device.bucket_scale is not None
    d, ids, _ = ix.search(q, k=5, mode="all")
    ie = np.asarray(ie)
    recall = np.mean(
        [len(set(ids[i].tolist()) & set(ie[i].tolist())) / 5 for i in range(len(q))]
    )
    assert recall >= 0.9, recall
    np.testing.assert_allclose(d, np.asarray(de), rtol=0.05, atol=0.05)
