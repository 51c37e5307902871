"""A cell of the benchmark cut to a size that a CPU test run holds."""
import lookup

ROWS = 2000


def small_cell(workload: str) -> dict:
    cell = lookup.cell(lookup.load_benchmark(), workload)
    cfg = cell["config"]
    rows = min(ROWS, cfg["rows"])
    c_max = max(4, int(rows ** 0.5))
    cell["config"] = dict(cfg, rows=rows, index=dict(cfg["index"], c_max=c_max))
    return cell
