"""DBSCAN preprocessing (paper §4.1, Algorithm 1) — TPU-native formulation.

The paper's sequential ExpandCluster recursion is replaced by a parallel
formulation with identical output semantics (DBSCAN's clustering is unique up
to border-point tie-breaking, which we resolve by nearest-core assignment):

1. *Core mask*: |N_eps(o)| >= MinPts, computed with blocked pairwise-distance
   tiles (never materializing the full N x N matrix).

Each phase streams its (block, N) distance tiles through the fused eps-graph
kernels in ``kernels/pairwise_l2.py`` (dispatch via ``kernels/ops``:
compiled Pallas on TPU, interpret under ``REPRO_FORCE_PALLAS=1``, pure-jnp
reference otherwise); ``kernel=False`` forces the in-place jnp formulation,
which tests/test_dbscan.py keeps as the oracle for the kernelized path.
2. *Core connectivity*: connected components of the eps-graph restricted to
   core points, via min-label propagation + pointer jumping inside a single
   jitted ``lax.while_loop`` (converges in O(graph diameter / 2^jumps) sweeps).
3. *Border points*: assigned to the cluster of their nearest core neighbor
   within eps; points with no core neighbor are NOISE.

Algorithm 1 lines 9-11 (partition extraction: pivot = cluster mean, radius =
max distance to pivot) are provided by ``partitions_from_labels``.  Noise is
assigned to the nearest pivot afterwards (production stores index everything;
documented deviation in DESIGN.md §3).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.ref import pairwise_sq_l2_ref

Array = jax.Array


@dataclass(frozen=True)
class DBSCANResult:
    labels: np.ndarray  # (N,) int32 contiguous cluster ids; -1 for noise
    n_clusters: int
    core_mask: np.ndarray  # (N,) bool
    n_iterations: int
    distance_computations: int  # total pairwise distances evaluated


def _pad_rows(x: Array, block: int) -> tuple[Array, int]:
    n = x.shape[0]
    pad = (-n) % block
    if pad:
        # Far-away pad rows: never within eps of anything real.
        x = jnp.concatenate([x, jnp.full((pad, x.shape[1]), 1e30, x.dtype)], axis=0)
    return x, n + pad


@functools.partial(
    jax.jit, static_argnames=("block", "min_pts", "max_iter", "kernel")
)
def _dbscan_device(
    x: Array, eps: float, *, min_pts: int, block: int, max_iter: int,
    kernel: bool = True,
):
    n = x.shape[0]
    xp, n_pad = _pad_rows(x, block)
    nb = n_pad // block
    eps_sq = jnp.asarray(eps, jnp.float32) ** 2
    sentinel = jnp.int32(n)

    def _block_rows(ib):
        return jax.lax.dynamic_slice_in_dim(xp, ib * block, block)

    # -- 1. core mask ------------------------------------------------------
    def _count_body(_, ib):
        if kernel:
            return None, ops.eps_count(_block_rows(ib), x, eps_sq)
        d = pairwise_sq_l2_ref(_block_rows(ib), x)
        return None, jnp.sum(d <= eps_sq, axis=1)

    _, counts = jax.lax.scan(_count_body, None, jnp.arange(nb))
    counts = counts.reshape(-1)[:n]
    core = counts >= min_pts  # (N,)

    # -- 2. min-label propagation over core-core eps edges ------------------
    labels0 = jnp.where(core, jnp.arange(n, dtype=jnp.int32), sentinel)

    def _sweep(labels):
        def body(_, ib):
            if kernel:
                return None, ops.eps_min_label(
                    _block_rows(ib), x, labels, core, eps_sq
                )
            d = pairwise_sq_l2_ref(_block_rows(ib), x)
            adj = (d <= eps_sq) & core[None, :]
            cand = jnp.where(adj, labels[None, :], sentinel)
            return None, jnp.min(cand, axis=1)

        _, new = jax.lax.scan(body, None, jnp.arange(nb))
        new = jnp.minimum(new.reshape(-1)[:n], labels)
        new = jnp.where(core, new, labels)
        # pointer jumping (path halving), x3
        ext = jnp.concatenate([new, jnp.array([sentinel], jnp.int32)])
        for _ in range(3):
            jumped = ext[jnp.clip(new, 0, n)]
            new = jnp.where(core & (jumped < new), jumped, new)
            ext = jnp.concatenate([new, jnp.array([sentinel], jnp.int32)])
        return new

    def cond(state):
        labels, prev, it = state
        return (it < max_iter) & jnp.any(labels != prev)

    def step(state):
        labels, _, it = state
        return _sweep(labels), labels, it + 1

    labels, _, iters = jax.lax.while_loop(
        cond, step, (_sweep(labels0), labels0, jnp.int32(1))
    )

    # -- 3. border points: nearest core neighbor within eps -----------------
    def _border_body(_, ib):
        if kernel:
            dmin, lab = ops.eps_nearest_core(_block_rows(ib), x, labels, core)
            return None, jnp.where(dmin <= eps_sq, lab, sentinel)
        d = pairwise_sq_l2_ref(_block_rows(ib), x)
        d = jnp.where(core[None, :], d, jnp.inf)
        j = jnp.argmin(d, axis=1)
        dmin = jnp.take_along_axis(d, j[:, None], axis=1)[:, 0]
        lab = labels[j]
        return None, jnp.where(dmin <= eps_sq, lab, sentinel)

    _, border = jax.lax.scan(_border_body, None, jnp.arange(nb))
    border = border.reshape(-1)[:n]
    final = jnp.where(core, labels, border)
    return final, core, iters


def dbscan(
    x,
    eps: float,
    min_pts: int,
    *,
    block: int = 1024,
    max_iter: int = 64,
    kernel: bool = True,
) -> DBSCANResult:
    """Run DBSCAN; returns contiguous labels (-1 = noise) on host.

    ``kernel=True`` (default) streams each phase through the fused eps-graph
    kernels (kernels/ops dispatch); ``kernel=False`` keeps the in-place jnp
    formulation — the oracle the kernel path is tested against.
    """
    x = jnp.asarray(x, jnp.float32)
    n = int(x.shape[0])
    block = int(min(block, max(128, n)))
    labels, core, iters = _dbscan_device(
        x, float(eps), min_pts=int(min_pts), block=block, max_iter=max_iter,
        kernel=bool(kernel),
    )
    labels = np.asarray(labels)
    core = np.asarray(core)
    iters = int(iters)
    # renumber to contiguous ids; sentinel (== n) -> -1
    out = np.full(n, -1, np.int32)
    valid = labels < n
    uniq, inv = np.unique(labels[valid], return_inverse=True)
    out[valid] = inv.astype(np.int32)
    n_pad = n + ((-n) % block)
    # sweeps: core-count pass + (iters propagation) + border pass, each n_pad*n
    dist_count = (iters + 2) * n_pad * n
    return DBSCANResult(
        labels=out,
        n_clusters=int(uniq.size),
        core_mask=core,
        n_iterations=iters,
        distance_computations=int(dist_count),
    )


def partitions_from_labels(
    x, labels: np.ndarray, n_clusters: int, *, assign_noise: bool = True
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Algorithm 1, lines 9-11: pivots (cluster means), radii (max distance
    to pivot), and the final object->partition assignment.

    Noise points (label -1) are assigned to their nearest pivot (radii are
    re-expanded accordingly) when ``assign_noise``.
    """
    x = np.asarray(x, np.float32)
    labels = np.asarray(labels).copy()
    if n_clusters == 0:
        # Degenerate: everything is noise -> single partition.
        pivot = x.mean(axis=0, keepdims=True)
        radii = np.array([np.sqrt(((x - pivot) ** 2).sum(-1)).max()], np.float32)
        return pivot.astype(np.float32), radii, np.zeros(len(x), np.int32)
    pivots = np.zeros((n_clusters, x.shape[1]), np.float64)
    counts = np.zeros(n_clusters, np.int64)
    np.add.at(pivots, labels[labels >= 0], x[labels >= 0])
    np.add.at(counts, labels[labels >= 0], 1)
    pivots = (pivots / np.maximum(counts[:, None], 1)).astype(np.float32)
    if assign_noise and (labels < 0).any():
        noise = np.where(labels < 0)[0]
        d = ((x[noise, None, :] - pivots[None, :, :]) ** 2).sum(-1)
        labels[noise] = d.argmin(axis=1).astype(np.int32)
    radii = np.zeros(n_clusters, np.float32)
    d_all = np.sqrt(((x - pivots[labels]) ** 2).sum(-1))
    np.maximum.at(radii, labels, d_all.astype(np.float32))
    return pivots, radii, labels.astype(np.int32)
