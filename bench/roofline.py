"""Operations and bytes that a search batch's bucket scan cannot do without.

The count reads only the visit set, through the executor's own counters,
so it is the same whatever implements the scan:

* FLOPs: ``2 * D * sum_q distances_q`` -- one multiply-add per coordinate
  for every real member of every bucket a query visits (``distances``
  counts real members, not bucket padding; D is the real dimension, not
  the 128-lane pad);
* bytes: ``D * itemsize * max_q distances_q`` plus the queries read and the
  (distance, id) pairs written -- the members of the largest visit set have
  to be read at least once, by any implementation.

A share of the roofline is the least time those take on the chip, the
larger of FLOPs over the peak rate and bytes over the memory bandwidth,
divided by the time the kernel took.  The peak rate is the chip's highest
float rate (bf16), so the share stays a lower bound for f32 work too.
"""
from __future__ import annotations

import numpy as np

RESULT_BYTES = 8  # one f32 distance and one i32 id per neighbour


def scan_work(distances: np.ndarray, dim: int, k: int, itemsize: int = 4) -> tuple[float, float]:
    """(FLOPs, bytes) of one batch from its (Q,) per-query distance counts."""
    distances = np.asarray(distances, np.float64)
    flops = 2.0 * dim * distances.sum()
    n_q = len(distances)
    bytes_ = dim * itemsize * distances.max(initial=0.0) + n_q * dim * 4 + n_q * k * RESULT_BYTES
    return float(flops), float(bytes_)


def roofline_share(flops: float, bytes_: float, seconds: float, peak_flops: float,
                   peak_bytes_per_s: float) -> tuple[float, str] | None:
    """(percent of the roofline, which bound binds) or None without a time."""
    if seconds <= 0 or (flops <= 0 and bytes_ <= 0):
        return None
    t_flops, t_bytes = flops / peak_flops, bytes_ / peak_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
