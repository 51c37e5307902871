"""MS SPACEV-1B stand-in: 100-d int8 web-document embeddings from a Gaussian mixture.

SPACEV-1B (big-ann-benchmarks, arXiv:2205.03763) holds Bing document
embeddings published as int8, 100 dimensions, searched under L2.  Nothing
of its value distribution is known here, so every choice below is assumed,
none fitted: each row is drawn from one of 32 Gaussian components, then
rounded to the nearest integer and clipped into [-128, 127], the int8
range.

The mixture: component weights Dirichlet(2); centres N(0, 16^2) in every
coordinate; per-coordinate spreads U(6, 10) (RMS), one per component, laid
along the axes of a random rotation with standard deviations falling as
``i ** -DECAY`` (i = 1..100), DECAY 0.73 as in ``sift_like``.  The pooled
within-component RMS is then about 8.2 a coordinate and a coordinate's
whole spread about 18, so a row's largest |value| lies near 50: clipping
touches about one coordinate in a million (well under 0.1% of rows), and
rows rarely reach +-127.  Output is float32 holding those integers.
"""
from __future__ import annotations

import numpy as np

COMPONENTS = 32
CENTRE = 16.0  # per-coordinate standard deviation of the component centres
SPREAD = (6.0, 10.0)  # per-coordinate within-component standard deviation (RMS)
DECAY = 0.73  # fall of a component's standard deviations along its axes
LO, HI = -128, 127  # the int8 range


def generate(n: int, dim: int, seed: int) -> np.ndarray:
    g = np.random.default_rng(seed)
    centres = g.normal(0.0, CENTRE, (COMPONENTS, dim))
    spreads = g.uniform(*SPREAD, COMPONENTS)
    counts = g.multinomial(n, g.dirichlet(np.full(COMPONENTS, 2.0)))
    axes = np.arange(1, dim + 1, dtype=np.float64) ** -DECAY
    axes /= np.sqrt(np.mean(axes**2))  # unit RMS, so a spread stays the per-coordinate RMS
    out = np.empty((n, dim), np.float32)
    lo = 0
    for c, s, m in zip(centres, spreads, counts):
        rotation = np.linalg.qr(g.standard_normal((dim, dim)))[0].astype(np.float32)
        z = g.standard_normal((m, dim), dtype=np.float32) * (s * axes).astype(np.float32)
        out[lo:lo + m] = c + z @ rotation.T
        lo += m
    np.rint(out, out=out)
    return np.clip(out, LO, HI, out=out)
