"""WARD stand-in: 5-d wearable motion-sensor windows in 13 activity classes.

Copied from ``repro.data.synthetic.ward_like`` so that the benchmark's data
cannot change with the program: 13 Gaussian classes, centres N(0, 25^2),
per-axis spreads U(0.5, 3.0), class sizes Dirichlet(2).
"""
from __future__ import annotations

import numpy as np


def generate(n: int, dim: int, seed: int) -> np.ndarray:
    g = np.random.default_rng(seed)
    n_classes = 13  # WARD's 13 activity classes
    centers = g.normal(size=(n_classes, dim)) * 25.0
    sizes = g.dirichlet(np.ones(n_classes) * 2.0)
    out = []
    for c, frac in zip(centers, sizes):
        m = max(1, int(n * frac))
        cov = g.uniform(0.5, 3.0, size=dim)
        out.append(c + g.normal(size=(m, dim)) * cov)
    x = np.concatenate(out)[:n]
    if len(x) < n:
        x = np.concatenate([x, g.normal(size=(n - len(x), dim)) * 25.0])
    return x.astype(np.float32)
