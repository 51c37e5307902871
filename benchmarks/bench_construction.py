"""Construction cost (paper Fig. 20): distance computations and comparisons
during index construction, per heuristic vs the BCCF-tree baseline.

The paper's Fig. 20 counts the TREE construction phase (its reported
36.6M/11.7M magnitudes exclude DBSCAN preprocessing, which would dominate);
we report the same tree-phase counters plus the preprocessing/overlap
counters separately for transparency.
"""
from __future__ import annotations

import time

from benchmarks.common import (
    METHODS,
    baseline_config,
    emit,
    facade_config,
    load_datasets,
)
from repro.compile_cache import enable_compile_cache
from repro.api import OverlapIndex


def run(full: bool = False, out: dict | None = None) -> None:
    for ds in load_datasets(full):
        for method in METHODS:
            t0 = time.perf_counter()
            rep = OverlapIndex.build(ds.x, facade_config(ds, method)).build_report
            dt = time.perf_counter() - t0
            derived = (
                f"dataset={ds.name};method={method};"
                f"tree_dist={rep.tree_distances};tree_cmp={rep.tree_comparisons};"
                f"dbscan_dist={rep.dbscan_distances};overlap_dist={rep.overlap_distances};"
                f"indexes={rep.n_indexes}"
            )
            emit(f"construction/{ds.name}/{method}", dt * 1e6, derived)
            if out is not None:
                out[f"{ds.name}/{method}"] = rep.__dict__ | {"detail": None}
        t0 = time.perf_counter()
        brep = OverlapIndex.baseline(ds.x, baseline_config(ds)).build_report
        dt = time.perf_counter() - t0
        emit(
            f"construction/{ds.name}/bccf-baseline", dt * 1e6,
            f"dataset={ds.name};method=bccf;tree_dist={brep.tree_distances};"
            f"tree_cmp={brep.tree_comparisons};indexes=1",
        )
        if out is not None:
            out[f"{ds.name}/bccf"] = {"tree_distances": brep.tree_distances,
                                      "tree_comparisons": brep.tree_comparisons}


if __name__ == "__main__":
    enable_compile_cache()
    run()
