"""DB1 stand-in: feature vectors of objects moving along tracks (IoVT).

Copied from ``repro.data.synthetic.tracking_like`` so that the benchmark's
data cannot change with the program: 24 smooth tracks of dense elongated
clusters with N(0, 0.8) sensor noise, and 3% uniform outliers.
"""
from __future__ import annotations

import numpy as np


def generate(n: int, dim: int, seed: int) -> np.ndarray:
    g = np.random.default_rng(seed)
    n_tracks = 24
    out = []
    remaining = n
    for t in range(n_tracks):
        m = remaining if t == n_tracks - 1 else max(1, int(n / n_tracks))
        remaining -= m
        start = g.normal(size=dim) * 40.0
        heading = g.normal(size=dim)
        heading /= np.linalg.norm(heading)
        ts = np.sort(g.uniform(0, 30.0, m))[:, None]
        pts = start + ts * heading * 2.0 + g.normal(size=(m, dim)) * 0.8
        out.append(pts)
    x = np.concatenate(out)[:n]
    # 3% uniform sensor-noise outliers
    k = max(1, int(0.03 * n))
    idx = g.choice(n, k, replace=False)
    x[idx] = g.uniform(x.min(), x.max(), size=(k, dim))
    return x.astype(np.float32)
