"""DBSCAN correctness: the parallel label-propagation formulation must match
a classic sequential reference on core-point clustering, and the kernelized
eps-graph path (``kernel=True``, fused reductions in kernels/pairwise_l2.py)
must match the in-place jnp formulation kept here as its oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dbscan, partitions_from_labels
from repro.kernels import ref
from repro.kernels.pairwise_l2 import (
    eps_count_pallas,
    eps_min_label_pallas,
    eps_nearest_core_pallas,
)


def _reference_dbscan(x: np.ndarray, eps: float, min_pts: int):
    """Textbook DBSCAN (Ester et al. 1996), O(n^2), for oracle use."""
    n = len(x)
    d = np.sqrt(((x[:, None, :] - x[None, :, :]) ** 2).sum(-1))
    neigh = [np.where(d[i] <= eps)[0] for i in range(n)]
    core = np.array([len(nb) >= min_pts for nb in neigh])
    labels = np.full(n, -1)
    cid = 0
    for i in range(n):
        if labels[i] != -1 or not core[i]:
            continue
        stack = [i]
        labels[i] = cid
        while stack:
            j = stack.pop()
            if not core[j]:
                continue
            for nb in neigh[j]:
                if labels[nb] == -1:
                    labels[nb] = cid
                    stack.append(nb)
        cid += 1
    return labels, core, cid


def _same_partition(a: np.ndarray, b: np.ndarray) -> bool:
    """Labelings equal up to renaming."""
    pa = {}
    for x_, y_ in zip(a.tolist(), b.tolist()):
        if x_ in pa and pa[x_] != y_:
            return False
        pa[x_] = y_
    return len(set(pa.values())) == len(pa)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("block", [64, 1000])
def test_dbscan_matches_reference(seed, block):
    g = np.random.default_rng(seed)
    centers = g.normal(size=(4, 4)) * 8
    x = np.concatenate(
        [c + g.normal(size=(120, 4)) for c in centers] + [g.uniform(-12, 12, (40, 4))]
    ).astype(np.float32)
    eps, min_pts = 1.2, 6
    ref_labels, ref_core, ref_k = _reference_dbscan(x, eps, min_pts)
    res = dbscan(x, eps, min_pts, block=block)
    assert (res.core_mask == ref_core).all()
    assert res.n_clusters == ref_k
    # Core-point clustering is unique: must match exactly up to renaming.
    c = ref_core
    assert _same_partition(res.labels[c], ref_labels[c])
    # Border points: our tie-break is nearest-core; both must agree on
    # noise-vs-clustered status.
    assert ((res.labels == -1) == (ref_labels == -1)).all()


def test_partitions_cover_everything(blob_data):
    x = blob_data[:800]
    res = dbscan(x, 1.5, 8)
    pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)
    n_clusters = max(res.n_clusters, 1)
    assert pivots.shape == (n_clusters, x.shape[1])
    assert (assign >= 0).all() and (assign < n_clusters).all()
    # radius covers every assigned object
    d = np.sqrt(((x - pivots[assign]) ** 2).sum(-1))
    assert (d <= radii[assign] + 1e-4).all()


def test_dbscan_all_noise():
    g = np.random.default_rng(3)
    x = g.uniform(-100, 100, size=(50, 6)).astype(np.float32)
    res = dbscan(x, 0.01, 5)
    assert res.n_clusters == 0
    assert (res.labels == -1).all()
    pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)
    assert pivots.shape[0] == 1  # degenerate single partition
    assert (assign == 0).all()


def test_dbscan_single_cluster():
    g = np.random.default_rng(4)
    x = g.normal(size=(200, 3)).astype(np.float32)
    res = dbscan(x, 3.0, 4)
    assert res.n_clusters == 1
    assert (res.labels == 0).all()


# --- kernelized eps-graph path vs the jnp oracle ---------------------------


def test_dbscan_kernel_path_matches_jnp(monkeypatch):
    """``kernel=True`` (the default, dispatched through kernels/ops — here
    forced onto the Pallas interpret path) must reproduce the in-place jnp
    formulation (``kernel=False``) exactly: same core mask, same clustering.
    """
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    g = np.random.default_rng(7)
    centers = g.normal(size=(3, 4)) * 10
    x = np.concatenate(
        [c + g.normal(size=(70, 4)) for c in centers] + [g.uniform(-15, 15, (30, 4))]
    ).astype(np.float32)
    res_k = dbscan(x, 1.3, 5, block=64)
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    res_j = dbscan(x, 1.3, 5, block=64, kernel=False)
    assert (res_k.core_mask == res_j.core_mask).all()
    assert res_k.n_clusters == res_j.n_clusters
    assert (res_k.labels == res_j.labels).all()


@pytest.mark.parametrize("qn,n", [(37, 117), (64, 64), (5, 200)])
def test_eps_kernels_match_ref(qn, n):
    """Each fused eps-graph kernel (interpret mode, ragged shapes exercising
    the pad/mask logic) against its pure-jnp oracle in kernels/ref.py."""
    g = np.random.default_rng(qn * 1000 + n)
    x = jnp.asarray(g.normal(size=(n, 6)).astype(np.float32) * 2)
    q = jnp.asarray(np.asarray(x[:qn]))
    labels = jnp.asarray(g.integers(0, n, size=n).astype(np.int32))
    core = jnp.asarray((g.random(n) < 0.6))
    # threshold near the median distance: both <= branches well-populated
    # (nudged off the exact data value so ulp-level reduction-order noise
    # between the tiled kernel and the one-shot reference cannot flip a <=)
    d_all = np.asarray(ref.pairwise_sq_l2_ref(q, x))
    eps_sq = jnp.float32(np.median(d_all) * 1.0009)
    kw = dict(bq=32, bn=32, interpret=True)

    cnt = eps_count_pallas(q, x, eps_sq, **kw)
    assert (np.asarray(cnt) == np.asarray(ref.eps_count_ref(q, x, eps_sq))).all()

    lab = eps_min_label_pallas(q, x, labels, core, eps_sq, **kw)
    ref_lab = ref.eps_min_label_ref(q, x, labels, core, eps_sq)
    assert (np.asarray(lab) == np.asarray(ref_lab)).all()

    dmin, nlab = eps_nearest_core_pallas(q, x, labels, core, **kw)
    rd, rl = ref.eps_nearest_core_ref(q, x, labels, core)
    # d2 = |q|^2 + |x|^2 - 2 q.x in f32 cancels: q is a slice of x, so the
    # nearest distances are ~0 and each side carries rounding error of order
    # (D + 2) * eps32 * (|q|^2 + |x|^2), summed in a different order by the
    # tiled kernel and the one-shot oracle (e.g. 0.0 vs 7.6e-6)
    sq = lambda a: (np.asarray(a) ** 2).sum(axis=1).max()
    atol = (x.shape[1] + 2) * np.finfo(np.float32).eps * (sq(q) + sq(x))
    np.testing.assert_allclose(np.asarray(dmin), np.asarray(rd), rtol=1e-6, atol=atol)
    assert (np.asarray(nlab) == np.asarray(rl)).all()


def test_eps_kernels_no_core_points():
    """Degenerate fleet: zero core points -> sentinel labels, +inf nearest
    distance — the all-noise DBSCAN branch."""
    g = np.random.default_rng(11)
    x = jnp.asarray(g.normal(size=(40, 3)).astype(np.float32))
    labels = jnp.arange(40, dtype=jnp.int32)
    core = jnp.zeros(40, bool)
    kw = dict(bq=32, bn=32, interpret=True)
    lab = eps_min_label_pallas(x, x, labels, core, jnp.float32(1.0), **kw)
    assert (np.asarray(lab) == 40).all()
    dmin, nlab = eps_nearest_core_pallas(x, x, labels, core, **kw)
    assert np.isinf(np.asarray(dmin)).all()
    assert (np.asarray(nlab) == 40).all()
