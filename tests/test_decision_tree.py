"""Decision stage (§4.3) invariants + BCCF tree construction tests."""
import numpy as np
import pytest

from repro.core import decide, dbscan, partitions_from_labels
from repro.core.bccf import build_tree
from repro.core.decision import Partition, rate_matrix


def _setup(blob_data, method):
    x = blob_data[:1200]
    res = dbscan(x, 1.5, 8)
    pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)
    rates = rate_matrix(method, x, pivots, radii, assign)
    groups, stats = decide(x, pivots, radii, assign, method=method, xi_min=0.3, xi_max=0.7,
                           rates=rates)
    return x, groups, stats


@pytest.mark.parametrize("method", ["vbm", "dbm", "obm"])
def test_decision_is_a_partition_of_objects(blob_data, method):
    """No object lost, none duplicated — regardless of merges/extractions."""
    x, groups, stats = _setup(blob_data, method)
    all_members = np.concatenate([g.members for g in groups])
    assert len(all_members) == len(x)
    assert len(np.unique(all_members)) == len(x)
    assert stats.n_final == len(groups)


@pytest.mark.parametrize("method", ["vbm", "dbm"])
def test_decision_geometry_and_links(blob_data, method):
    x, groups, _ = _setup(blob_data, method)
    for i, g in enumerate(groups):
        # radius covers members
        d = np.sqrt(((x[g.members] - g.pivot) ** 2).sum(-1))
        assert (d <= g.radius + 1e-3).all()
        # neighbor links are symmetric and valid
        for nb in g.neighbors:
            assert 0 <= nb < len(groups) and nb != i
            assert i in groups[nb].neighbors
        if g.is_overlap_index:
            assert len(g.neighbors) >= 1


def test_merge_all_when_thresholds_zero(blob_data):
    """xi_max=0 forces every overlapping pair to merge."""
    x = blob_data[:600]
    res = dbscan(x, 1.5, 8)
    pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)
    rates = rate_matrix("dbm", x, pivots, radii, assign)
    groups, _ = decide(x, pivots, radii, assign, method="dbm", xi_min=0.0, xi_max=0.0,
                       rates=rates)
    # every group disjoint from every other (or single group)
    for i, g in enumerate(groups):
        for j, h in enumerate(groups):
            if i < j:
                d = np.sqrt(((g.pivot - h.pivot) ** 2).sum())
                assert d >= g.radius + h.radius - 1e-3


@pytest.mark.parametrize("pivot_method", ["gh", "kmeans"])
def test_tree_invariants(blob_data, pivot_method):
    x = blob_data[:700]
    ids = np.arange(len(x))
    tree = build_tree(x, ids, c_max=30, pivot_method=pivot_method, seed=0)
    # every object in exactly one bucket
    got = np.sort(np.concatenate(tree.bucket_members))
    assert (got == ids).all()
    # bucket capacity respected
    assert max(len(b) for b in tree.bucket_members) <= 30
    # structure bookkeeping consistent
    s = tree.structure
    assert s.n_leaves == len(tree.bucket_members)
    assert s.n_internal == len(tree.node_children)
    assert sum(s.nodes_per_level.values()) == s.n_internal + s.n_leaves
    # binary tree: leaves = internal + 1
    assert s.n_leaves == s.n_internal + 1
    assert tree.counters.distances > 0 and tree.counters.comparisons > 0


def test_tree_radii_cover_subtree(blob_data):
    """Def. 12: node radii are max distance over the whole subtree."""
    x = blob_data[:400]
    tree = build_tree(x, np.arange(len(x)), c_max=25, pivot_method="gh", seed=1)

    def collect(node: int) -> np.ndarray:
        if node < 0:
            return tree.bucket_members[-(node + 1)]
        l, r = tree.node_children[node]
        return np.concatenate([collect(l), collect(r)])

    for nid in range(len(tree.node_children)):
        members = collect(nid)
        for side in (0, 1):
            d = np.sqrt(((x[members] - tree.node_pivots[nid, side]) ** 2).sum(-1))
            assert d.max() <= tree.node_radii[nid, side] + 1e-3


def test_gh_cheaper_than_kmeans(blob_data):
    """The paper's §4.3 rationale: GH construction needs fewer distances."""
    x = blob_data[:1000]
    t_gh = build_tree(x, np.arange(len(x)), c_max=32, pivot_method="gh", seed=0)
    t_km = build_tree(x, np.arange(len(x)), c_max=32, pivot_method="kmeans", seed=0)
    assert t_gh.counters.distances < t_km.counters.distances


def test_duplicate_points_dont_hang():
    x = np.ones((100, 4), np.float32)
    tree = build_tree(x, np.arange(100), c_max=10, pivot_method="gh", seed=0)
    assert sum(len(b) for b in tree.bucket_members) == 100
