"""Structure evaluation (paper Figs. 6-19): bucket-size distributions,
nodes per level, internal/leaf counts and tree heights, per heuristic and
dataset."""
from __future__ import annotations

import json
import time

import numpy as np

from benchmarks.common import METHODS, emit, facade_config, load_datasets
from repro.compile_cache import enable_compile_cache
from repro.api import OverlapIndex


def run(full: bool = False, out: dict | None = None) -> None:
    for ds in load_datasets(full):
        for method in METHODS:
            t0 = time.perf_counter()
            ix = OverlapIndex.build(ds.x, facade_config(ds, method))
            dt = time.perf_counter() - t0
            s = ix.build_report.detail["structure"]
            buckets = [b for t in s["trees"] for b in t["bucket_sizes"]]
            levels: dict[int, int] = {}
            for t in s["trees"]:
                for lv, n in t["nodes_per_level"].items():
                    levels[int(lv)] = levels.get(int(lv), 0) + n
            derived = (
                f"dataset={ds.name};method={method};trees={s['n_trees']};"
                f"internal={s['total_internal']};leaves={s['total_leaves']};"
                f"height={s['max_height']};bucket_mean={np.mean(buckets):.1f};"
                f"bucket_median={np.median(buckets):.0f};"
                f"bucket_max={max(buckets)};"
                f"peak_level={max(levels, key=levels.get)}"
            )
            emit(f"structure/{ds.name}/{method}", dt * 1e6, derived)
            if out is not None:
                out[f"{ds.name}/{method}"] = {
                    "structure": s, "levels": levels,
                    "bucket_mean": float(np.mean(buckets)),
                }


if __name__ == "__main__":
    enable_compile_cache()
    run()
