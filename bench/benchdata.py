"""A run's data and queries, made from ``--seed``.

The stored points are the configuration's generator output at its own data
seed, reflected by a sign flip of each coordinate drawn from ``--seed``.  A
reflection changes no rounding anywhere: every product, sum and distance of
the build is the same number, so every seed gets the same set of sizes (the
same clusters, indexes and bucket counts, hence the same compiled programs)
and different data.  A permutation of the coordinates would reorder the
sums, and at 250,000 WARD rows that moved the bucket count from seed to
seed.  Queries are stored points plus the generator's own noise, drawn from
``--seed``.
"""
from __future__ import annotations

import numpy as np

from lookup import generator

STREAMS = {"reflection": 1, "queries": 2, "sample": 3, "warmup": 4}


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, STREAMS[stream]]))


def dataset(cfg: dict, seed: int) -> np.ndarray:
    base = generator(cfg["generator"])(cfg["rows"], cfg["dim"], cfg["data_seed"])
    sign = np.where(rng(seed, "reflection").random(cfg["dim"]) < 0.5, -1.0, 1.0)
    return base * sign.astype(np.float32)


def queries(x: np.ndarray, noise: float, batches: int, batch: int, g: np.random.Generator) -> np.ndarray:
    """(batches, batch, D) f32: stored points plus N(0, noise^2) per coordinate."""
    n = batches * batch
    q = x[g.choice(len(x), n)] + g.normal(0.0, noise, (n, x.shape[1]))
    return q.astype(np.float32).reshape(batches, batch, x.shape[1])
