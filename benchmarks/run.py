"""Benchmark orchestrator — one module per paper table/figure.

Output format: ``name,us_per_call,derived`` CSV rows.

  structure     — paper Figs. 6-19  (tree structure evaluation)
  construction  — paper Fig. 20    (build-phase distance/comparison counts)
  search        — paper Fig. 21    (kNN search efficiency vs k)
  retrieval     — framework feature microbench (kNN-LM datastore scan)
  roofline      — §Roofline rollup from the dry-run artifacts

``--full`` uses paper-scale dataset sizes (62,702 / 1M rows); the default
is scaled for CI.  ``--only <name>`` runs one suite.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache  # noqa: E402

from benchmarks import (  # noqa: E402
    bench_construction,
    bench_retrieval,
    bench_search,
    bench_structure,
    roofline,
)

SUITES = {
    "structure": bench_structure.run,
    "construction": bench_construction.run,
    "search": bench_search.run,
    "retrieval": bench_retrieval.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale datasets")
    ap.add_argument("--only", choices=list(SUITES))
    ap.add_argument("--json-out", default="experiments/bench_results.json")
    args = ap.parse_args()

    results: dict[str, dict] = {}
    suites = {args.only: SUITES[args.only]} if args.only else SUITES
    print("name,us_per_call,derived")
    enable_compile_cache()
    for name, fn in suites.items():
        out: dict = {}
        fn(full=args.full, out=out)  # a failing suite fails the run
        results[name] = out
    path = Path(args.json_out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(results, indent=1, default=str))


if __name__ == "__main__":
    main()
