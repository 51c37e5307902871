"""The SIFT shape (128-d, L2, DBM) through the facade, against a plain
float64 brute force.

The data is SIFT-shaped: non-negative orientation histograms from a few
seeded components, put through Lowe's post-processing (clip at 0,
normalise, clip at 0.2, renormalise, x 512, rounded into [0, 255]), at a
CPU test's size.  At D = 128 nothing is padded to the lanes, and VBM's
volume rates vanish (``test_overlap.py``), so the index runs DBM.
``mode="all"`` must return the exact neighbours: ids equal to the oracle's
up to ties, squared distances within (D + 2) eps32 (|q|^2 + |x|^2) of the
true ones (the tolerance unit of ``chip_smoke.py``).  Each case runs on the
jnp path and on the Pallas kernels in interpret mode.
"""
import jax
import numpy as np
import pytest

from repro.api import Config, IndexConfig, OverlapIndex, SearchConfig
from repro.kernels import ops as kops

ROWS, DIM = 3000, 128
F32_EPS = float(np.finfo(np.float32).eps)


def sift_shaped(n: int, seed: int, components: int = 4) -> np.ndarray:
    g = np.random.default_rng(seed)
    centres = g.standard_normal((components, DIM)) ** 2
    centres /= np.linalg.norm(centres, axis=1, keepdims=True)
    v = centres[g.integers(0, components, n)] + 0.03 * g.standard_normal((n, DIM))
    v = np.maximum(v, 0.0)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    v = np.minimum(v, 0.2)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return np.clip(np.rint(512.0 * v), 0, 255).astype(np.float32)


@pytest.fixture(scope="module")
def sift_index():
    x = sift_shaped(ROWS, 16)
    cfg = Config(
        index=IndexConfig(method="dbm", eps=240.0, min_pts=16, c_max=int(ROWS ** 0.5)),
        search=SearchConfig(quantize=False),
    )
    return x, cfg, OverlapIndex.build(x, cfg)


def test_sift_shape_builds_several_indexes(sift_index):
    x, cfg, ix = sift_index
    build = ix.metrics()["build"]
    assert build["indexes"] == ix.n_indexes >= 2
    assert build["buckets"] == ix.forest.n_buckets
    assert ix.forest.bucket_x.shape[2] == DIM
    assert np.array_equal(np.sort(ix.forest.bucket_ids[ix.forest.bucket_mask]), np.arange(ROWS))


@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("path", ["jnp", "pallas-interpret"])
def test_sift_shape_exact_search_matches_brute_force(sift_index, k, path, monkeypatch):
    x, cfg, _ = sift_index
    g = np.random.default_rng(k)
    q = (x[g.choice(ROWS, 16, replace=False)] + g.normal(0.0, 17.0, (16, DIM))).astype(np.float32)
    traced = []
    if path == "pallas-interpret":
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
        kernel = kops.bucket_scan_topk_pallas
        monkeypatch.setattr(kops, "bucket_scan_topk_pallas",
                            lambda *a, **kw: traced.append(a[1].shape) or kernel(*a, **kw))
    jax.clear_caches()  # the kernel choice is read while tracing
    # a fresh facade over the same forest: its plans are traced on this path
    base = sift_index[2]
    ix = OverlapIndex._wire(x, base.forest, cfg, base.build_report)
    res = ix.search(q, k=k, mode="all")
    monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
    jax.clear_caches()
    # the kernel scanned lane-aligned 128-d tiles, nothing padded
    assert [s[-1] for s in traced] == ([DIM] if path == "pallas-interpret" else [])

    q64, x64 = q.astype(np.float64), x.astype(np.float64)
    qq, xx = (q64 ** 2).sum(1), (x64 ** 2).sum(1)
    d2 = np.maximum(qq[:, None] + xx[None, :] - 2.0 * q64 @ x64.T, 0.0)
    want = np.argsort(d2, axis=1, kind="stable")[:, :k]
    tol = (DIM + 2) * F32_EPS * (qq[:, None] + xx[None, :])
    rows = np.arange(len(q))[:, None]
    got = res.ids
    assert got.shape == (len(q), k) and (got >= 0).all()
    assert all(len(set(r)) == k for r in got.tolist())
    err = np.abs(res.dists.astype(np.float64) ** 2 - d2[rows, got])
    assert (err <= tol[rows, got]).all(), err.max()
    # rank by rank the returned neighbours are the oracle's, up to ties
    by_rank = np.take_along_axis(got, np.argsort(d2[rows, got], axis=1, kind="stable"), 1)
    assert (np.abs(d2[rows, by_rank] - d2[rows, want]) <= tol[rows, want]).all()
    assert (by_rank == want).mean() > 0.99
