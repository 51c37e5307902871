"""SIFT-1M stand-in: 128-d SIFT descriptors drawn from a Gaussian mixture.

The ann-benchmarks ``sift-128-euclidean`` set (TEXMEX ANN_SIFT1M) holds
SIFT descriptors: histograms of gradient orientations, post-processed as
Lowe (2004, section 6.1) prescribes.  Here each descriptor's raw histogram
is drawn from one of 32 Gaussian components, then goes through that same
post-processing: clipped at 0 (a histogram has no negative bins),
L2-normalised, clipped at 0.2, renormalised, scaled by 512 and rounded to
an integer in [0, 255].

The mixture: component weights Dirichlet(2); centres 0.3 of one histogram
shape that every component shares plus 0.7 of their own, each with
squared-normal bins (a few strong orientations, many weak ones) at unit
norm; per-bin spreads U(0.025, 0.045) of the centre's norm, one per
component, laid along the axes of a random rotation with standard
deviations falling as ``i ** -DECAY`` (i = 1..128).  ``DECAY`` is the one
number fitted to the published set: ann-benchmarks (arXiv:1807.05614)
gives sift-128-euclidean a mean local intrinsic dimensionality of 21.9,
and at 0.73 the output reads 21.9 (maximum-likelihood estimate over each
of 1,000 separate draws' 100 nearest neighbours among 1,000,000 rows).
No row is left out for being an outlier: whatever DBSCAN calls noise
stays in.
"""
from __future__ import annotations

import numpy as np

COMPONENTS = 32
SHARED = 0.3  # weight of the histogram shape every component shares
SPREAD = (0.025, 0.045)  # per-bin standard deviation (RMS), relative to a unit centre
DECAY = 0.73  # fall of a component's standard deviations along its axes: mean LID 21.9
CLIP = 0.2  # Lowe's clip of a normalised descriptor's bins
SCALE = 512.0  # normalised descriptor -> integer bins, as in the TEXMEX files


def _unit(a: np.ndarray) -> np.ndarray:
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


def generate(n: int, dim: int, seed: int) -> np.ndarray:
    g = np.random.default_rng(seed)
    shared = _unit(g.standard_normal(dim) ** 2)
    centres = SHARED * shared + (1.0 - SHARED) * _unit(g.standard_normal((COMPONENTS, dim)) ** 2)
    spreads = g.uniform(*SPREAD, COMPONENTS)
    counts = g.multinomial(n, g.dirichlet(np.full(COMPONENTS, 2.0)))
    axes = np.arange(1, dim + 1, dtype=np.float64) ** -DECAY
    axes /= np.sqrt(np.mean(axes**2))  # unit RMS, so a spread stays the per-bin RMS
    out = np.empty((n, dim), np.float32)
    lo = 0
    for c, s, m in zip(centres, spreads, counts):
        rotation = np.linalg.qr(g.standard_normal((dim, dim)))[0].astype(np.float32)
        z = g.standard_normal((m, dim), dtype=np.float32) * (s * axes).astype(np.float32)
        out[lo:lo + m] = c + z @ rotation.T
        lo += m
    np.maximum(out, 0.0, out=out)
    out /= np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-12)
    np.minimum(out, CLIP, out=out)
    out /= np.maximum(np.linalg.norm(out, axis=1, keepdims=True), 1e-12)
    out *= SCALE
    np.rint(out, out=out)
    return np.clip(out, 0.0, 255.0, out=out)
