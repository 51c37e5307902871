"""Fused bucket gather + squared-L2 + running top-k merge Pallas kernel.

The forest search hot loop (core/knn.py STEP 2b) evaluates, per while-loop
step, the next ``beam`` buckets of every query: gather the selected bucket
members, compute query->member distances, and merge them into the running
per-query top-k.  The jnp formulation materializes a ``(Q, beam, C, D)``
gather plus a ``(Q, kk + beam*C)`` merge buffer through HBM on *every* step
— at production bucket capacities that is the entire search cost.

This kernel fuses the three stages in VMEM:

* the bucket ids selected for this step (``bsel``, (Q, beam)) and the
  per-(query, bucket) active mask (``act``) ride in as **scalar-prefetch**
  operands, flattened to 1-D, so the grid's DMA engine gathers exactly the
  ``(C, D)`` bucket tiles the step needs straight from the flattened
  ``bucket_x`` in HBM — the (Q, beam, C, D) intermediate never exists;
* queries move in blocks of ``QB = 8`` rows (one f32 sublane tile): each
  program computes ``(8, D) x (C, D)^T`` on the MXU for the bucket of ONE
  row of its block and merges into that row only;
* the running ``(8, kk)`` top-k block (values + global object ids) stays
  resident in the output VMEM block across the beam and row axes,
  maintained with ``topk.extract_topk``'s k-step masked-min extraction.

Grid: ``(Q/8, beam, 8)``.  The output block depends only on the first
axis, so the inner two revisit it (the accumulation pattern of topk.py's
N axis), and each query merges its buckets in beam order.

An int8 variant dequantizes the gathered bucket tile in-register against
per-member scales (``ops.quantize_datastore`` layout), quartering the HBM
traffic of the member gather — the memory-roofline lever for serving.

Validated against ``ref.bucket_scan_topk_ref`` in tests/test_bucket_scan.py
(interpret mode on CPU, compiled on real TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk import extract_topk

Array = jax.Array


# Queries per grid block: one f32 sublane tile.  Every per-query operand
# moves in (8, .) blocks (the chip's tiling rule); the grid walks the 8 rows
# of a block one at a time and only that row's state is updated.
QB = 8
# Words of SMEM each scalar-prefetch operand (bsel, act) may take per call.
# A v5e core has 1 MiB of SMEM; larger query batches run as several calls.
SMEM_WORDS = 32 * 1024


def _scan_kernel(
    bsel_ref,  # scalar prefetch (Qp * beam,) i32, row-major (query, beam)
    act_ref,  # scalar prefetch (Qp * beam,) i32
    q_ref,  # (QB, Dp) the query block
    x_ref,  # (1, Cp, Dp) gathered bucket tile (f32 or int8)
    ids_ref,  # (1, 1, Cp) i32, -1 pad
    *rest,  # [scale_ref (1, 1, Cp) f32,] top_d, top_i, o_val, o_idx
    kk: int,
    beam: int,
    quantized: bool,
):
    if quantized:
        scale_ref, top_d_ref, top_i_ref, o_val_ref, o_idx_ref = rest
    else:
        top_d_ref, top_i_ref, o_val_ref, o_idx_ref = rest
    g = pl.program_id(0)
    b = pl.program_id(1)
    r = pl.program_id(2)

    @pl.when((b == 0) & (r == 0))
    def _init():
        o_val_ref[...] = top_d_ref[...]
        o_idx_ref[...] = top_i_ref[...]

    x = x_ref[0].astype(jnp.float32)  # (Cp, Dp)
    if quantized:
        x = x * scale_ref[0].T  # per-member dequant scales as a column
    qv = q_ref[...].astype(jnp.float32)  # (QB, Dp)
    qq = jnp.sum(qv * qv, axis=1, keepdims=True)  # (QB, 1)
    xx = jnp.sum(x * x, axis=1)  # (Cp,)
    cross = jax.lax.dot_general(
        qv, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (QB, Cp)
    d2 = jnp.maximum(qq + xx[None, :] - 2.0 * cross, 0.0)
    # only row r of the block owns this bucket; the others stay as they are
    row = jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0) == r
    qi = g * QB + r
    live = (ids_ref[0] >= 0) & row & (act_ref[qi * beam + b] > 0)
    d2 = jnp.where(live, d2, jnp.inf)
    cand_i = jnp.where(live, ids_ref[0], -1)

    kkp = o_val_ref.shape[1]
    vals = jnp.concatenate([o_val_ref[...], d2], axis=1)  # (QB, kkp + Cp)
    idxs = jnp.concatenate([o_idx_ref[...], cand_i], axis=1)
    new_v, new_i = extract_topk(vals, idxs, kk, kkp)
    o_val_ref[...] = jnp.where(row, new_v, o_val_ref[...])
    o_idx_ref[...] = jnp.where(row, new_i, o_idx_ref[...])


def _pad_to(a: Array, axis: int, mult: int, value=0) -> Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _pad_multiples(interpret: bool) -> tuple[int, int]:
    """(lane, C-axis) padding multiples for the kernel's blocks.

    C is the sublane axis of the (1, C, D) member blocks AND the lane axis
    of the (1, 1, C) id/scale blocks, so compiled mode gives it the full
    lane multiple (which also satisfies the int8 sublane-32 requirement).
    The interpreter has no tiling constraints; small multiples keep the CPU
    test sweeps exercising the padding paths the compiled kernel relies on.
    """
    return (8, 2) if interpret else (128, 128)


def _member_rows(a: Array, cmult: int, value) -> Array:
    """(NB, C) per-member operand -> (NB, 1, Cp): one lane-major row per
    bucket, so a one-bucket block spans the array's last two dims.
    Idempotent on an already prepared (NB, 1, Cp) operand."""
    if a.ndim == 2:
        a = a[:, None, :]
    return _pad_to(a, 2, cmult, value=value)


def prepad_buckets(
    bucket_x: Array,
    bucket_ids: Array,
    scale: Array | None = None,
    *,
    interpret: bool = False,
) -> tuple[Array, Array, Array | None]:
    """Lay out the per-datastore operands for the kernel's blocks ONCE.

    Pads ``bucket_x`` to (NB, Cp, Dp) and turns ``bucket_ids``/``scale``
    into (NB, 1, Cp) rows.  ``bucket_scan_topk_pallas`` does the same on
    every call, as a no-op on operands prepared here; done inside a search
    while-loop it would copy the whole datastore each step, so callers that
    loop (core/knn.py) prepare at upload time.
    """
    lane, cmult = _pad_multiples(interpret)
    xp = _pad_to(_pad_to(bucket_x, 2, lane), 1, cmult)
    idsp = _member_rows(bucket_ids, cmult, -1)
    scalep = None if scale is None else _member_rows(
        scale.astype(jnp.float32), cmult, 0.0
    )
    return xp, idsp, scalep


@functools.partial(jax.jit, static_argnames=("interpret",))
def bucket_scan_topk_pallas(
    q: Array,  # (Q, D) f32
    bucket_x: Array,  # (NB, C, D) f32 or int8
    bucket_ids: Array,  # (NB, C) i32, -1 pad (or prepad_buckets' layout)
    bsel: Array,  # (Q, beam) i32 bucket selection for this step
    act: Array,  # (Q, beam) bool/int — bucket still inside the bound
    top_d: Array,  # (Q, kk) running top-k squared distances (ascending)
    top_i: Array,  # (Q, kk) running top-k object ids
    scale: Array | None = None,  # (NB, C) f32 when bucket_x is int8
    *,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """One fused scan step; returns the merged (top_d, top_i), both (Q, kk)."""
    qn = q.shape[0]
    beam = bsel.shape[1]
    kk = top_d.shape[1]
    quantized = scale is not None
    rows = max(QB, SMEM_WORDS // beam // QB * QB)
    if qn > rows:  # keep the scalar-prefetch operands inside SMEM
        parts = [
            bucket_scan_topk_pallas(
                q[lo:lo + rows], bucket_x, bucket_ids, bsel[lo:lo + rows],
                act[lo:lo + rows], top_d[lo:lo + rows], top_i[lo:lo + rows],
                scale, interpret=interpret,
            )
            for lo in range(0, qn, rows)
        ]
        return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))

    lane, _ = _pad_multiples(interpret)
    xp, idsp, scalep = prepad_buckets(
        bucket_x, bucket_ids, scale, interpret=interpret
    )
    qp = _pad_to(_pad_to(q.astype(jnp.float32), 1, lane), 0, QB)
    qpn = qp.shape[0]
    kkp = kk + (-kk) % lane
    top_dp = _pad_to(top_d.astype(jnp.float32), 1, lane, value=jnp.inf)
    top_dp = _pad_to(top_dp, 0, QB, value=jnp.inf)
    top_ip = _pad_to(_pad_to(top_i.astype(jnp.int32), 1, lane, value=-1), 0, QB, value=-1)
    # pad query rows select bucket 0 and are never active; flattened to 1-D
    # so the scalar-prefetch operands take Q * beam words of SMEM
    bsel_f = _pad_to(bsel.astype(jnp.int32), 0, QB).reshape(-1)
    act_f = _pad_to(act.astype(jnp.int32), 0, QB).reshape(-1)

    cp, dp = xp.shape[1], xp.shape[2]

    def member(g, b, r, bsel, act):
        return (bsel[(g * QB + r) * beam + b], 0, 0)

    def query_block(g, b, r, bsel, act):
        return (g, 0)

    member_row = pl.BlockSpec((1, 1, cp), member)
    in_specs = [
        pl.BlockSpec((QB, dp), query_block),
        pl.BlockSpec((1, cp, dp), member),
        member_row,
        *([member_row] if quantized else []),
        pl.BlockSpec((QB, kkp), query_block),
        pl.BlockSpec((QB, kkp), query_block),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # the output block depends on the first axis only: it stays
        # resident while each of its 8 rows merges its beam buckets
        grid=(qpn // QB, beam, QB),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((QB, kkp), query_block),
            pl.BlockSpec((QB, kkp), query_block),
        ],
    )
    vals, idxs = pl.pallas_call(
        functools.partial(_scan_kernel, kk=kk, beam=beam, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qpn, kkp), jnp.float32),
            jax.ShapeDtypeStruct((qpn, kkp), jnp.int32),
        ],
        interpret=interpret,
    )(
        bsel_f,
        act_f,
        qp,
        xp,
        idsp,
        *([scalep] if quantized else []),
        top_dp,
        top_ip,
    )
    return vals[:qn, :kk], idxs[:qn, :kk]
