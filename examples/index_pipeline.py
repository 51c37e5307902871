"""The paper's three-stage pipeline, step by step, with the overlap matrices
printed — the 'explainer' example.

    PYTHONPATH=src python examples/index_pipeline.py
"""
import sys

sys.path.insert(0, "src")

import jax.numpy as jnp
import numpy as np

from repro.core import dbscan, decide, overlap_matrix, partitions_from_labels
from repro.core.forest import build_forest
from repro.data.synthetic import ward_like


def main() -> None:
    x = ward_like(6_000)
    print(f"(i) preprocessing: DBSCAN over {len(x)} x {x.shape[1]} objects")
    res = dbscan(x, eps=2.0, min_pts=23)
    print(f"    {res.n_clusters} clusters, {(res.labels < 0).sum()} noise pts, "
          f"{res.n_iterations} propagation sweeps")
    pivots, radii, assign = partitions_from_labels(x, res.labels, res.n_clusters)

    print("(ii) overlap estimation (paper Defs. 7-11):")
    rates_of = {}
    for method in ("vbm", "dbm", "obm"):
        rates = rates_of[method] = np.asarray(overlap_matrix(
            method, jnp.asarray(pivots), jnp.asarray(radii),
            x=jnp.asarray(x), assign=jnp.asarray(assign)))
        iu = np.triu_indices_from(rates, 1)
        print(f"    {method}: mean={rates[iu].mean():.4f} max={rates[iu].max():.4f} "
              f"pairs>0: {(rates[iu] > 0).sum()}/{len(iu[0])}")

    print("(iii) decision-making (xi_min=0.4, xi_max=0.8), VBM:")
    groups, stats = decide(x, pivots, radii, assign,
                           method="vbm", xi_min=0.4, xi_max=0.8, rates=rates_of["vbm"])
    print(f"    merged pairs: {stats.n_merged_pairs}, overlap indexes: "
          f"{stats.n_overlap_indexes}, low-overlap moves: {stats.n_low_moves}")
    print(f"    final groups: {stats.n_final}")

    forest = build_forest(x, groups, c_max=int(np.sqrt(len(x))), pivot_method="gh")
    s = forest.aggregate_structure()
    print(f"    forest: {s['n_trees']} trees, {s['total_leaves']} buckets, "
          f"height {s['max_height']}, mean bucket fill {s['bucket_fill_mean']:.1f}")
    for i, g in enumerate(groups):
        tag = " (overlap index)" if g.is_overlap_index else ""
        print(f"      index {i}: {len(g.members)} objects, r={g.radius:.2f}, "
              f"neighbors={g.neighbors}{tag}")


if __name__ == "__main__":
    main()
