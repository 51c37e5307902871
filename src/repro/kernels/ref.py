"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel in kernels/ is validated against these references over
shape/dtype sweeps in tests/test_kernels_*.py (interpret mode on CPU,
compiled on real TPU).

This module owns the squared-L2 expansion ``|q|^2 + |x|^2 - 2 q.x`` for the
jnp side (``core.metric`` uses it too).  Its contractions run at
``Precision.HIGHEST``: on a TPU the default f32 matmul takes bf16 passes,
which would move distances, pruning bounds and eps tests far past f32
rounding; on a CPU the flag changes nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_sq_l2_ref(q: Array, x: Array) -> Array:
    """(Q, D) x (N, D) -> (Q, N) squared L2, via the MXU-friendly expansion."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qq = jnp.sum(q * q, axis=-1)[:, None]
    xx = jnp.sum(x * x, axis=-1)[None, :]
    cross = jnp.matmul(q, x.T, precision=_HIGHEST)
    return jnp.maximum(qq + xx - 2.0 * cross, 0.0)


def eps_count_ref(q: Array, x: Array, eps_sq: Array) -> Array:
    """(Q,) i32 eps-neighbor counts — DBSCAN's core test."""
    d2 = pairwise_sq_l2_ref(q, x)
    return jnp.sum(d2 <= eps_sq, axis=1).astype(jnp.int32)


def eps_min_label_ref(
    q: Array, x: Array, labels: Array, core: Array, eps_sq: Array
) -> Array:
    """(Q,) i32 min label over core eps-neighbors; N (sentinel) if none."""
    d2 = pairwise_sq_l2_ref(q, x)
    adj = (d2 <= eps_sq) & (core != 0)[None, :]
    sentinel = jnp.int32(x.shape[0])
    return jnp.min(jnp.where(adj, labels[None, :].astype(jnp.int32), sentinel), axis=1)


def eps_nearest_core_ref(
    q: Array, x: Array, labels: Array, core: Array
) -> tuple[Array, Array]:
    """Per query: (d2 to nearest core point, its label); (+inf, N) if none."""
    d2 = pairwise_sq_l2_ref(q, x)
    d2 = jnp.where((core != 0)[None, :], d2, jnp.inf)
    j = jnp.argmin(d2, axis=1)
    dmin = jnp.take_along_axis(d2, j[:, None], axis=1)[:, 0]
    lab = jnp.where(
        jnp.isinf(dmin), jnp.int32(x.shape[0]), labels.astype(jnp.int32)[j]
    )
    return dmin, lab


def knn_topk_ref(q: Array, x: Array, k: int) -> tuple[Array, Array]:
    """Exact k smallest squared-L2 distances + indices: (Q, k), (Q, k)."""
    d2 = pairwise_sq_l2_ref(q, x)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def masked_knn_topk_ref(q: Array, x: Array, mask: Array, k: int) -> tuple[Array, Array]:
    """As knn_topk_ref but positions with mask==False excluded (dist=+inf)."""
    d2 = pairwise_sq_l2_ref(q, x)
    d2 = jnp.where(mask[None, :], d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx


def bucket_scan_topk_ref(
    q: Array,
    bucket_x: Array,
    bucket_ids: Array,
    bsel: Array,
    act: Array,
    top_d: Array,
    top_i: Array,
    scale: Array | None = None,
    offset: Array | None = None,
) -> tuple[Array, Array, Array]:
    """One forest-scan step: gather selected buckets, distance, top-k merge.

    q (Q, D); bucket_x (NB, C, D) f32 or int8 (then ``scale`` (NB, C) holds
    per-member dequant scales and ``offset`` (D,), or None for zero, the
    per-dimension offsets: ``x = xq * scale + offset``); bsel/act (Q, beam);
    top_d/top_i (Q, kk) the running per-query top-k (squared distances
    ascending, object ids).  Members with id < 0 (padding) and buckets with
    act == False contribute nothing.  Returns the merged (top_d, top_i) and (Q,) i32 inserts: the
    candidates that entered the running top-k, bucket by bucket in beam
    order, as the kernel's insertion merge counts them (``merge_counting``).
    """
    qn, kk = top_d.shape
    q = q.astype(jnp.float32)
    bx = bucket_x[bsel]  # (Q, beam, C, D)
    if scale is not None:
        bx = bx.astype(jnp.float32) * scale[bsel][..., None].astype(jnp.float32)
        if offset is not None:
            bx = bx + offset.astype(jnp.float32)
    else:
        bx = bx.astype(jnp.float32)
    bids = bucket_ids[bsel]  # (Q, beam, C)
    live = (bids >= 0) & act[:, :, None]
    d2 = (
        jnp.sum(q * q, axis=-1)[:, None, None]
        + jnp.sum(bx * bx, axis=-1)
        - 2.0 * jnp.einsum("qbcd,qd->qbc", bx, q, precision=_HIGHEST)
    )
    d2 = jnp.where(live, jnp.maximum(d2, 0.0), jnp.inf)
    cand_i = jnp.where(live, bids, -1)
    inserts = jnp.zeros((qn,), jnp.int32)
    for b in range(bsel.shape[1]):
        top_d, top_i, n = merge_counting(top_d, top_i, d2[:, b], cand_i[:, b])
        inserts = inserts + n
    return top_d, top_i, inserts


def merge_counting(
    top_d: Array, top_i: Array, cand_d: Array, cand_i: Array
) -> tuple[Array, Array, Array]:
    """``topk_by_distance_then_id`` of [top | candidates] at the top's width,
    and (Q,) i32: how many candidates it holds.  Where a candidate equals an
    entry of the top in (distance, id) the entry stays: the insertion merge
    takes only candidates strictly below its k-th entry."""
    kk = top_d.shape[1]
    d = jnp.concatenate([top_d, cand_d], axis=1)
    ids = jnp.concatenate([top_i, cand_i], axis=1)
    src = jnp.concatenate(
        [jnp.zeros(top_d.shape, jnp.int32), jnp.ones(cand_d.shape, jnp.int32)], axis=1
    )
    d, ids, src = jax.lax.sort((d, ids, src), dimension=1, num_keys=3)
    return d[:, :kk], ids[:, :kk], jnp.sum(src[:, :kk], axis=1, dtype=jnp.int32)


def topk_by_distance_then_id(d: Array, ids: Array, k: int) -> tuple[Array, Array]:
    """The ``k`` smallest (distance, id) pairs per row, ascending.  Equal
    distances go to the smaller id, whatever order the candidates arrived
    in: the tie rule of every top-k merge on the search path (this oracle,
    ``topk.insert_topk`` in the kernels, the cross-shard merge), so an
    answer depends neither on visit order nor on which shard held a member.
    Masked candidates carry (+inf, -1)."""
    d, ids = jax.lax.sort((d, ids), dimension=1, num_keys=2)
    return d[:, :k], ids[:, :k]


def pairwise_sq_l2_int8_ref(q: Array, x_q: Array, scale: Array) -> Array:
    """Quantized-datastore distances: x stored int8 with per-row scales.

    Dequantized row j is ``x_q[j] * scale[j]``; distances are computed against
    the f32 queries.  (ADC-style retrieval; beyond-paper optimization.)
    """
    x = x_q.astype(jnp.float32) * scale[:, None].astype(jnp.float32)
    return pairwise_sq_l2_ref(q, x)
