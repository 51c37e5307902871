"""Fused streaming distance + top-k Pallas kernel.

For decode-time retrieval (kNN-LM) the naive two-pass plan

    d2 = pairwise(q, datastore)   # (Q, N) materialized in HBM
    topk(d2, k)                   # second HBM pass

writes and re-reads an (Q, N) f32 matrix.  At datastore shard sizes of
10^6+ rows this is pure memory-roofline waste.  This kernel keeps the
running per-query top-k (values + global indices) resident in the output
VMEM blocks while streaming datastore tiles through the MXU, so the (Q, N)
matrix never exists.

Grid: (Q/bq, N/bn); the N axis is sequential (accumulation over the same
output block).  D is kept whole inside the block (padded to 128): retrieval
key dims (<= 8K) fit VMEM comfortably at bq = bn = 256.

Top-k maintenance: per N-tile, iteratively extract the k smallest of
[running top-k | tile distances] (k is small and static — k extraction
steps of a (bq, kp + bn) masked min, ``extract_topk``, with the running
top-k padded to kp, a lane multiple).  Indices are tracked through the same
selection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def extract_topk(vals, idxs, kk, width):
    """k-step min-extraction of the ``kk`` smallest ``vals`` per row.

    ``vals``/``idxs`` are (R, W); returns (R, width) ascending values and
    their ids, ``inf``/``-1`` beyond ``kk`` and wherever the pool ran dry.
    Equal values go to the smaller id (``ref.topk_by_distance_then_id``'s
    rule), one occurrence per step.  Written with compares and masked
    reductions only: no gather, no argmin, no lane concatenation of single
    columns, so the same body compiles for the chip and runs in the
    interpreter.
    """
    rows, w = vals.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def step(t, carry):
        vals, out_v, out_i = carry
        m = jnp.min(vals, axis=1, keepdims=True)  # (R, 1)
        tied = vals == m
        picked = jnp.min(jnp.where(tied, idxs, big), axis=1, keepdims=True)
        first = jnp.min(
            jnp.where(tied & (idxs == picked), pos, w), axis=1, keepdims=True
        )
        hit = pos == first
        # An inf extraction means the pool ran dry: what is left is masked
        # or padded candidates (id -1, or the flat scan's rows past N),
        # so inf => -1 matches the oracle's contract.
        picked = jnp.where(jnp.isinf(m), -1, picked)
        out_v = jnp.where(col == t, m, out_v)
        out_i = jnp.where(col == t, picked, out_i)
        return jnp.where(hit, jnp.inf, vals), out_v, out_i

    init = (
        vals,
        jnp.full((rows, width), jnp.inf, jnp.float32),
        jnp.full((rows, width), -1, jnp.int32),
    )
    _, out_v, out_i = jax.lax.fori_loop(0, kk, step, init)
    return out_v, out_i


def _knn_topk_kernel(q_ref, x_ref, o_val_ref, o_idx_ref, *, k: int, bn: int, n_real: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_val_ref[...] = jnp.full_like(o_val_ref, jnp.inf)
        o_idx_ref[...] = jnp.full_like(o_idx_ref, -1)

    q = q_ref[...].astype(jnp.float32)  # (bq, D)
    x = x_ref[...].astype(jnp.float32)  # (bn, D)
    qq = jnp.sum(q * q, axis=1)
    xx = jnp.sum(x * x, axis=1)
    cross = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.maximum(qq[:, None] + xx[None, :] - 2.0 * cross, 0.0)  # (bq, bn)
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, (d2.shape[0], bn), 1)
    d2 = jnp.where(gidx < n_real, d2, jnp.inf)

    vals = jnp.concatenate([o_val_ref[...], d2], axis=1)  # (bq, kp + bn)
    idxs = jnp.concatenate([o_idx_ref[...], gidx], axis=1)
    new_v, new_i = extract_topk(vals, idxs, k, o_val_ref.shape[1])
    o_val_ref[...] = new_v
    o_idx_ref[...] = new_i


def _pad_to(a: Array, axis: int, mult: int) -> Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret"))
def knn_topk_pallas(
    q: Array,
    x: Array,
    *,
    k: int,
    bq: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """k smallest squared-L2 distances of each query against the datastore.

    Returns (values (Q, k) ascending, indices (Q, k)); indices are -1 / inf
    when the datastore has fewer than k rows.
    """
    qn = q.shape[0]
    n = x.shape[0]
    qp = _pad_to(q.astype(jnp.float32), 0, bq)
    qp = _pad_to(qp, 1, 128)
    xp = _pad_to(x.astype(jnp.float32), 0, bn)
    xp = _pad_to(xp, 1, 128)
    grid = (qp.shape[0] // bq, xp.shape[0] // bn)
    kp = k + (-k) % 128  # lane-aligned running top-k, tail stays inf / -1
    kernel = functools.partial(_knn_topk_kernel, k=k, bn=bn, n_real=n)
    vals, idxs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, qp.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, xp.shape[1]), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], kp), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], kp), jnp.int32),
        ],
        interpret=interpret,
    )(qp, xp)
    return vals[:qn, :k], idxs[:qn, :k]
