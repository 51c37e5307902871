"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/lookup.py``).  ``--trace 0`` prints
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics read from
a profiler trace of the same window.  Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here, before any import

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import lookup  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = lookup.cell(lookup.load_benchmark(), args.workload)
    except LookupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 3
    harness.report(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
