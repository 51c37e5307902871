"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the program's own run (a short window at the
cell's load, compared with the reference exactly as a benchmark run
compares it), then the control on the same sampled batches: the reference
put in the program's place at one precision step lower
(``reference.control_search``).  Prints one JSON line per seed, then the
lower reading of each number (the largest the program gave) and the upper
(the smallest the control gave).  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402
import lookup  # noqa: E402
import reference  # noqa: E402


def readings(cell: dict, seed: int, seconds: float) -> dict:
    """Program and control readings of one seed."""
    cfg, traffic = cell["config"], cell["traffic"]
    t = time.perf_counter()
    x, pool, ix = harness.prepare(cfg, traffic, seed)
    setup = time.perf_counter() - t
    batches, window_s = harness.window(ix, pool, traffic, seconds)
    shape = {"indexes": ix.n_indexes, "buckets": ix.forest.n_buckets}
    del ix
    program, _, _, _ = harness.check_answers(x, pool, batches, cfg["limits"], seed)
    xx = reference.sq_norms(x)
    control = []
    for j in harness.sample(len(batches), seed):
        q = pool[batches[j].pool_index]
        d, i = reference.control_search(q, x, traffic["k"])
        control.append({n: float(v.max()) if n != "bad_ids" else float(v.sum())
                        for n, v in reference.compare_rows(q, x, i, d, xx).items()})
    return {"seed": seed, "setup_s": setup, "batches": len(batches), "window_s": window_s,
            **shape, "program": program, "control": reference.merge(control)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = lookup.cell(lookup.load_benchmark(), args.workload)
    harness.devices(cell["chips"], require_chip=True)
    harness.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    names = rows[0]["program"]
    print(json.dumps({
        "workload": args.workload,
        "lower": {n: max(r["program"][n] for r in rows) for n in names},
        "upper": {n: min(r["control"][n] for r in rows) for n in names},
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
