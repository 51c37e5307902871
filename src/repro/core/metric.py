"""Metric-space primitives (paper §2, Definitions 1-5).

All distance computations are batched, jittable, and dispatch to the Pallas
pairwise kernel (``repro.kernels.ops``) above a size threshold; below it they
use the pure-jnp path (identical math, cheaper dispatch).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels import ref as kref

Array = jax.Array

# ---------------------------------------------------------------------------
# Distance functions d : S x S -> R+  (p1-p4 of Definition 1)
# ---------------------------------------------------------------------------


def sq_l2(x: Array, y: Array) -> Array:
    """Squared euclidean distance between single objects (D,) x (D,)."""
    d = x - y
    return jnp.sum(d * d)


def l2(x: Array, y: Array) -> Array:
    return jnp.sqrt(jnp.maximum(sq_l2(x, y), 0.0))


def l1(x: Array, y: Array) -> Array:
    return jnp.sum(jnp.abs(x - y))


def cosine(x: Array, y: Array) -> Array:
    """Cosine *distance* (1 - cosine similarity). Not a metric (fails p4 in
    general) but commonly used for embedding datastores; exposed for the
    retrieval layer, never for the tree-bound math (which assumes p4)."""
    nx = jnp.linalg.norm(x) + 1e-12
    ny = jnp.linalg.norm(y) + 1e-12
    return 1.0 - jnp.dot(x, y) / (nx * ny)


METRICS: dict[str, Callable[[Array, Array], Array]] = {
    "l2": l2,
    "sq_l2": sq_l2,
    "l1": l1,
    "cosine": cosine,
}


# ---------------------------------------------------------------------------
# Batched pairwise distances
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric", "use_kernel"))
def pairwise(q: Array, x: Array, *, metric: str = "l2", use_kernel: bool = True) -> Array:
    """Pairwise distance matrix (Q, N) between rows of q (Q, D) and x (N, D).

    ``use_kernel`` routes the L2 family through the Pallas tiled kernel when
    shapes are MXU-friendly; the fallback is the jnp expansion that the kernel
    is validated against (kernels/ref.py).
    """
    if metric in ("l2", "sq_l2"):
        if use_kernel:
            sq = kops.pairwise_sq_l2(q, x)
        else:
            sq = kref.pairwise_sq_l2_ref(q, x)
        return sq if metric == "sq_l2" else jnp.sqrt(jnp.maximum(sq, 0.0))
    if metric == "l1":
        return jnp.sum(jnp.abs(q[:, None, :] - x[None, :, :]), axis=-1)
    if metric == "cosine":
        qn = q / (jnp.linalg.norm(q, axis=-1, keepdims=True) + 1e-12)
        xn = x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)
        return 1.0 - jnp.matmul(qn, xn.T, precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown metric {metric!r}")


def distances_to_point(x: Array, p: Array, *, metric: str = "l2") -> Array:
    """Distances (N,) from every row of x (N, D) to a single point p (D,)."""
    return pairwise(p[None, :], x, metric=metric, use_kernel=False)[0]


def check_metric_axioms(d: Callable, pts: Array, atol: float = 1e-5) -> dict[str, bool]:
    """Empirically check p1-p4 on a point sample. Used by property tests."""
    n = pts.shape[0]
    dm = jax.vmap(lambda a: jax.vmap(lambda b: d(a, b))(pts))(pts)
    non_neg = bool(jnp.all(dm >= -atol))
    sym = bool(jnp.allclose(dm, dm.T, atol=atol))
    ident = bool(jnp.all(jnp.abs(jnp.diag(dm)) <= atol))
    # For all (i, j, k): d(i,j) + d(j,k) >= d(i,k).
    tri = bool(jnp.all(dm[:, :, None] + dm[None, :, :] >= dm[:, None, :] - atol))
    return {"non_negativity": non_neg, "symmetry": sym, "identity": ident, "triangle": tri}
