"""Tiled squared-L2 pairwise-distance Pallas kernel.

The retrieval hot loop (DBSCAN eps-graph, bucket bounds, bucket evaluation,
datastore scan) is dominated by ``(Q, D) x (N, D) -> (Q, N)`` distance
matrices.  On TPU this is an MXU matmul plus rank-1 norm updates:

    d2[i, j] = ||q_i||^2 + ||x_j||^2 - 2 <q_i, x_j>

Grid: (Q/bq, N/bn, D/bd) with accumulation over the contraction axis (last
grid dimension; same output block revisited, so ``dimension_semantics``
marks it "arbitrary" and the two output axes "parallel").  Per-step VMEM
working set is ``bq*bd + bn*bd + bq*bn`` f32 — defaults (256, 256, 256)
give 768 KB, comfortably inside the ~16 MB v5e VMEM while keeping MXU
tiles 128-aligned.

The int8 variant dequantizes the datastore tile in-register (per-row scale),
halving (vs bf16) or quartering (vs f32) the HBM traffic of a datastore
scan — the memory-roofline lever for decode-time retrieval.

The ``eps_*`` kernels below fuse DBSCAN's eps-neighbor-graph reductions
(core counting, min-label propagation, nearest-core border assignment) into
the same tiled distance stream: grid (Q/bq, N/bn) with D whole inside the
block (padded to 128) and the N axis sequential over a (bq, 1)-shaped
running output, so the per-query distance row is thresholded/reduced
in-register and the (Q, N) block never reaches HBM.  Per-point operands
along N (labels, core flags, int8 scales) travel as lane-major (1, N) rows
in (1, bn) blocks.

Every contraction runs at ``Precision.HIGHEST``: the chip's default f32
matmul takes bf16 passes, too coarse for distances that decide eps
membership, pruning bounds and exact top-k answers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_HIGHEST = jax.lax.Precision.HIGHEST
# (Q, N, D) grid: output tiles are independent, D accumulates into them
_ACCUMULATE_D = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)
# (Q, N) grid of the eps kernels: N reduces into a (bq, 1) running output
_REDUCE_N = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _cross(q, x):
    """(bq, D) x (bn, D) -> (bq, bn) inner products on the MXU in f32."""
    return jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _pairwise_kernel(q_ref, x_ref, o_ref):
    """One (bq, bn) output tile, accumulated over D-axis grid steps."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)  # (bq, bd)
    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    qq = jnp.sum(q * q, axis=1)  # (bq,)
    xx = jnp.sum(x * x, axis=1)  # (bn,)
    o_ref[...] += qq[:, None] + xx[None, :] - 2.0 * _cross(q, x)


def _pairwise_int8_kernel(q_ref, x_ref, scale_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) * scale_ref[...].T  # (1, bn) row -> column
    qq = jnp.sum(q * q, axis=1)
    xx = jnp.sum(x * x, axis=1)
    o_ref[...] += qq[:, None] + xx[None, :] - 2.0 * _cross(q, x)


def _pad_to(a: Array, axis: int, mult: int, value: float = 0.0) -> Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("bq", "bn", "bd", "interpret")
)
def pairwise_sq_l2_pallas(
    q: Array,
    x: Array,
    *,
    bq: int = 256,
    bn: int = 256,
    bd: int = 256,
    interpret: bool = False,
) -> Array:
    """(Q, D) x (N, D) -> (Q, N) squared L2 distances (f32)."""
    qn, d = q.shape
    n = x.shape[0]
    qp = _pad_to(q.astype(jnp.float32), 0, bq)
    qp = _pad_to(qp, 1, bd)
    xp = _pad_to(x.astype(jnp.float32), 0, bn)
    xp = _pad_to(xp, 1, bd)
    grid = (qp.shape[0] // bq, xp.shape[0] // bn, qp.shape[1] // bd)
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], xp.shape[0]), jnp.float32),
        compiler_params=_ACCUMULATE_D,
        interpret=interpret,
    )(qp, xp)
    return jnp.maximum(out[:qn, :n], 0.0)


# --- fused DBSCAN eps-graph reductions -------------------------------------
# Shared tile shape: q (bq, Dp), x (bn, Dp) with Dp the whole (128-padded)
# feature axis; each kernel reduces its (bq, bn) in-register distance tile
# straight into a (bq, 1) running output.  ``n_real`` masks the N padding.


def _tile_sq_l2(q_ref, x_ref):
    q = q_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    qq = jnp.sum(q * q, axis=1)
    xx = jnp.sum(x * x, axis=1)
    return jnp.maximum(qq[:, None] + xx[None, :] - 2.0 * _cross(q, x), 0.0)


def _eps_count_kernel(q_ref, x_ref, eps_ref, o_ref, *, bn, n_real):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    d2 = _tile_sq_l2(q_ref, x_ref)
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    within = (d2 <= eps_ref[0, 0]) & (gidx < n_real)
    o_ref[...] += jnp.sum(within, axis=1, keepdims=True).astype(jnp.int32)


def _eps_min_label_kernel(q_ref, x_ref, lab_ref, core_ref, eps_ref, o_ref, *, bn, n_real):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, n_real)

    d2 = _tile_sq_l2(q_ref, x_ref)
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    adj = (d2 <= eps_ref[0, 0]) & (core_ref[...] != 0) & (gidx < n_real)
    cand = jnp.where(adj, lab_ref[...], jnp.int32(n_real))
    o_ref[...] = jnp.minimum(o_ref[...], jnp.min(cand, axis=1, keepdims=True))


def _eps_nearest_core_kernel(
    q_ref, x_ref, lab_ref, core_ref, o_d_ref, o_lab_ref, *, bn, n_real
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_d_ref[...] = jnp.full_like(o_d_ref, jnp.inf)
        o_lab_ref[...] = jnp.full_like(o_lab_ref, n_real)

    d2 = _tile_sq_l2(q_ref, x_ref)
    gidx = j * bn + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where((core_ref[...] != 0) & (gidx < n_real), d2, jnp.inf)
    dmin = jnp.min(d2, axis=1, keepdims=True)  # (bq, 1)
    # first-index-wins inside the tile: the label at the lowest position
    # attaining the minimum (an argmin + gather, as masked reductions)
    none = jnp.int32(n_real)
    first = jnp.min(jnp.where(d2 == dmin, gidx, none), axis=1, keepdims=True)
    lab = jnp.min(jnp.where(gidx == first, lab_ref[...], none), axis=1, keepdims=True)
    # strict <: the earliest tile keeps ties, matching a full-row argmin
    better = dmin < o_d_ref[...]
    o_lab_ref[...] = jnp.where(better, lab, o_lab_ref[...])
    o_d_ref[...] = jnp.where(better, dmin, o_d_ref[...])


def _n_row(a: Array, bn: int) -> Array:
    """Per-point (N,) operand -> lane-major (1, Np) int32 row."""
    return _pad_to(a.astype(jnp.int32), 0, bn)[None, :]


def _eps_operands(q, x, bq, bn):
    qp = _pad_to(q.astype(jnp.float32), 0, bq)
    qp = _pad_to(qp, 1, 128)
    xp = _pad_to(x.astype(jnp.float32), 0, bn)
    xp = _pad_to(xp, 1, 128)
    grid = (qp.shape[0] // bq, xp.shape[0] // bn)
    qspec = pl.BlockSpec((bq, qp.shape[1]), lambda i, j: (i, 0))
    xspec = pl.BlockSpec((bn, xp.shape[1]), lambda i, j: (j, 0))
    nspec = pl.BlockSpec((1, bn), lambda i, j: (0, j))  # per-point (1, N) rows
    espec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))  # replicated scalar
    ospec = pl.BlockSpec((bq, 1), lambda i, j: (i, 0))
    return qp, xp, grid, qspec, xspec, nspec, espec, ospec


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def eps_count_pallas(
    q: Array,
    x: Array,
    eps_sq: Array,
    *,
    bq: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> Array:
    """(Q,) i32: per query, |{j : d2(q, x_j) <= eps_sq}|."""
    qn, n = q.shape[0], x.shape[0]
    qp, xp, grid, qspec, xspec, _, espec, ospec = _eps_operands(q, x, bq, bn)
    out = pl.pallas_call(
        functools.partial(_eps_count_kernel, bn=bn, n_real=n),
        grid=grid,
        in_specs=[qspec, xspec, espec],
        out_specs=ospec,
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.int32),
        compiler_params=_REDUCE_N,
        interpret=interpret,
    )(qp, xp, jnp.asarray(eps_sq, jnp.float32).reshape(1, 1))
    return out[:qn, 0]


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def eps_min_label_pallas(
    q: Array,
    x: Array,
    labels: Array,
    core: Array,
    eps_sq: Array,
    *,
    bq: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> Array:
    """(Q,) i32: min label over eps-neighbors that are core; N (= len(x))
    when a query has none — DBSCAN's sentinel convention."""
    qn, n = q.shape[0], x.shape[0]
    qp, xp, grid, qspec, xspec, nspec, espec, ospec = _eps_operands(q, x, bq, bn)
    out = pl.pallas_call(
        functools.partial(_eps_min_label_kernel, bn=bn, n_real=n),
        grid=grid,
        in_specs=[qspec, xspec, nspec, nspec, espec],
        out_specs=ospec,
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.int32),
        compiler_params=_REDUCE_N,
        interpret=interpret,
    )(
        qp, xp,
        _n_row(labels, bn),
        _n_row(core, bn),
        jnp.asarray(eps_sq, jnp.float32).reshape(1, 1),
    )
    return out[:qn, 0]


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def eps_nearest_core_pallas(
    q: Array,
    x: Array,
    labels: Array,
    core: Array,
    *,
    bq: int = 256,
    bn: int = 256,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Per query: (d2 to the nearest core point, that point's label) —
    (+inf, N) when no core point exists.  First-index tie-breaking matches
    ``jnp.argmin`` over the masked full row (the jnp oracle)."""
    qn, n = q.shape[0], x.shape[0]
    qp, xp, grid, qspec, xspec, nspec, _, ospec = _eps_operands(q, x, bq, bn)
    dmin, lab = pl.pallas_call(
        functools.partial(_eps_nearest_core_kernel, bn=bn, n_real=n),
        grid=grid,
        in_specs=[qspec, xspec, nspec, nspec],
        out_specs=[ospec, ospec],
        out_shape=[
            jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((qp.shape[0], 1), jnp.int32),
        ],
        compiler_params=_REDUCE_N,
        interpret=interpret,
    )(
        qp, xp,
        _n_row(labels, bn),
        _n_row(core, bn),
    )
    return dmin[:qn, 0], lab[:qn, 0]


@functools.partial(
    jax.jit, static_argnames=("bq", "bn", "bd", "interpret")
)
def pairwise_sq_l2_int8_pallas(
    q: Array,
    x_q: Array,
    scale: Array,
    *,
    bq: int = 256,
    bn: int = 256,
    bd: int = 256,
    interpret: bool = False,
) -> Array:
    """f32 queries vs int8 per-row-quantized datastore -> (Q, N) sq-L2."""
    qn, d = q.shape
    n = x_q.shape[0]
    qp = _pad_to(q.astype(jnp.float32), 0, bq)
    qp = _pad_to(qp, 1, bd)
    xp = _pad_to(x_q, 0, bn)
    xp = _pad_to(xp, 1, bd)
    sp = _pad_to(scale.astype(jnp.float32), 0, bn)[None, :]  # (1, Np) row
    grid = (qp.shape[0] // bq, xp.shape[0] // bn, qp.shape[1] // bd)
    out = pl.pallas_call(
        _pairwise_int8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bd), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], xp.shape[0]), jnp.float32),
        compiler_params=_ACCUMULATE_D,
        interpret=interpret,
    )(qp, xp, sp)
    return jnp.maximum(out[:qn, :n], 0.0)
