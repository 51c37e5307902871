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

Top-k maintenance: per N-tile, ``insert_topk`` merges the tile's
distances into the running top-k (padded to kp, a lane multiple), one
entering candidate per trip, so a tile whose distances all lie above every
row's k-th costs one gate and no trip.  Indices travel with their values.
The bucket-scan kernel merges with the same function.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def insert_topk(top_v, top_i, vals, idxs, kk):
    """Merge candidates into a sorted running top-k, one insertion per step.

    ``top_v``/``top_i`` are (R, width): each row ascending by (value, id),
    lanes ``>= kk`` at (inf, -1).  ``vals``/``idxs`` are (R, W) candidates,
    (inf, -1) where masked; an inf candidate never enters.  Returns the
    merged (top_v, top_i), the ``kk`` smallest (value, id) pairs of the
    union taken in (value, id) order, duplicates included, and (R, 1) i32:
    each row's candidates inserted.

    The loop runs while some row holds a candidate (value, id)
    lexicographically strictly below that row's k-th entry. Each step, every
    such row takes its smallest candidate (smaller value, then smaller id,
    then first position), shifts its entries from the candidate's place one
    lane right and writes it there; the other rows stay as they are. The
    k-th entry only falls, so the trip count is the most candidates that
    enter any one row, and 0 where none can enter. Written with compares,
    masked reductions and one lane roll: no gather, no argmin, so the same
    body compiles for the chip and runs in the interpreter.
    """
    rows, w = vals.shape
    width = top_v.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)

    def below(v, i, bv, bi):
        return (v < bv) | ((v == bv) & (i < bi))

    def kth(tv, ti):
        at = col == kk - 1
        return (
            jnp.min(jnp.where(at, tv, jnp.inf), axis=1, keepdims=True),
            jnp.min(jnp.where(at, ti, big), axis=1, keepdims=True),
        )

    def cond(c):
        pool, _, _, kv, ki, _ = c
        # a full reduction of an f32 mask: what the chip reduces to a scalar
        return jnp.max(jnp.where(below(pool, idxs, kv, ki), 1.0, 0.0)) > 0.0

    def body(c):
        pool, tv, ti, kv, ki, n = c
        m = jnp.min(pool, axis=1, keepdims=True)  # (R, 1)
        tied = pool == m
        mi = jnp.min(jnp.where(tied, idxs, big), axis=1, keepdims=True)
        first = jnp.min(jnp.where(tied & (idxs == mi), pos, w), axis=1, keepdims=True)
        enter = below(m, mi, kv, ki)  # (R, 1)
        # entries below the candidate keep their lanes; its place is lane 0
        # or the first lane whose left neighbour stays; lanes after it take
        # their left neighbour
        sv = pltpu.roll(tv, shift=1, axis=1)
        si = pltpu.roll(ti, shift=1, axis=1)
        stay = below(tv, ti, m, mi)
        here = (col == 0) | below(sv, si, m, mi)
        nv = jnp.where(stay, tv, jnp.where(here, m, sv))
        ni = jnp.where(stay, ti, jnp.where(here, mi, si))
        if kk < width:
            nv = jnp.where(col < kk, nv, jnp.inf)
            ni = jnp.where(col < kk, ni, -1)
        tv = jnp.where(enter, nv, tv)
        ti = jnp.where(enter, ni, ti)
        pool = jnp.where(enter & (pos == first), jnp.inf, pool)
        return (pool, tv, ti, *kth(tv, ti), n + enter.astype(jnp.int32))

    init = (vals, top_v, top_i, *kth(top_v, top_i), jnp.zeros((rows, 1), jnp.int32))
    _, top_v, top_i, _, _, n = jax.lax.while_loop(cond, body, init)
    return top_v, top_i, n


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

    new_v, new_i, _ = insert_topk(o_val_ref[...], o_idx_ref[...], d2, gidx, k)
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
