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
* queries move in blocks of ``QB = 8`` rows (one f32 sublane tile), and
  one program serves a whole block: it brings in the 8 bucket tiles its
  rows selected at this beam slot (one ``BlockSpec`` per row on the same
  bucket store, each indexed through ``bsel``), computes each row's
  distances to its own tile (``(8, D) x (C, D)^T`` on the MXU, row r kept),
  and merges all 8 rows at once into their running top-k;
* the merge (``topk.insert_topk``) is a gated insertion: while some row
  holds a candidate lexicographically strictly below its k-th (distance,
  id) entry, each such row inserts its smallest candidate at its place in
  the sorted top-k (a one-lane shift right from there) and drops its
  k-th.  The trip count is the most candidates that enter any of the 8
  rows, set by the data: 0 once the scan's later buckets hold nothing
  better.  The result is the ``kk`` smallest (distance, id) pairs of the
  union, bit for bit, duplicates included;
* the running ``(8, kk)`` top-k block (values + global object ids) stays
  resident in the output VMEM block across the beam axis, beside a count
  block: each row's insertions in this call, which the search sums into
  ``SearchStats.topk_inserts``.

Grid: ``(Q/8, beam)``.  The output block depends only on the first axis,
so the beam axis revisits it (the accumulation pattern of topk.py's N
axis), and each query merges its buckets in beam order.  Every block is
double-buffered, 2 x 8 member tiles among them; where that outgrows the
compiler's default scoped VMEM the call raises its own limit
(``_vmem_limit``), from the shapes it is given.

An int8 variant brings in int8 member tiles, a quarter of the f32 tile's
bytes, and dequantizes each in VMEM as ``x = xq * scale[member] +
offset[dim]`` (``ops.quantize_members``' format: per-member scales as
``(1, 1, C)`` rows beside the ids, the per-dimension offset as one ``(1,
D)`` block whose index map is constant) before the same f32 ``HIGHEST``
distance math as the f32 variant.  On byte-valued members the format is
exact (scale 1), so the distances are the f32 variant's, bit for bit.

Validated against ``ref.bucket_scan_topk_ref`` in tests/test_bucket_scan.py
(interpret mode on CPU, compiled on real TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk import insert_topk

Array = jax.Array


# Queries per grid block: one f32 sublane tile.  Every per-query operand
# moves in (8, .) blocks (the chip's tiling rule); each program brings in the
# 8 bucket tiles its rows selected and merges all 8 rows at once.
QB = 8
# Words of SMEM each scalar-prefetch operand (bsel, act) may take per call.
# A v5e core has 1 MiB of SMEM; larger query batches run as several calls.
SMEM_WORDS = 32 * 1024
# VMEM a kernel may take unless its call asks for more (the v5e compiler's
# scoped default).
SCOPED_VMEM_BYTES = 16 * 1024 * 1024


def _scan_kernel(
    bsel_ref,  # scalar prefetch (Qp * beam,) i32, row-major (query, beam)
    act_ref,  # scalar prefetch (Qp * beam,) i32
    q_ref,  # (QB, Dp) the query block
    *rest,  # QB x (1, Cp, Dp) tiles, QB x (1, 1, Cp) ids, [QB x (1, 1, Cp)
    #         scales, (1, Dp) offset,] top_d, top_i, o_val, o_idx, o_ins
    kk: int,
    beam: int,
    quantized: bool,
):
    x_refs, ids_refs = rest[:QB], rest[QB:2 * QB]
    scale_refs = rest[2 * QB:3 * QB] if quantized else None
    offset_ref = rest[3 * QB] if quantized else None
    top_d_ref, top_i_ref, o_val_ref, o_idx_ref, o_ins_ref = rest[(3 * QB + 1 if quantized else 2 * QB):]
    g = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        o_val_ref[...] = top_d_ref[...]
        o_idx_ref[...] = top_i_ref[...]
        o_ins_ref[...] = jnp.zeros_like(o_ins_ref)

    qv = q_ref[...].astype(jnp.float32)  # (QB, Dp)
    qq = jnp.sum(qv * qv, axis=1, keepdims=True)  # (QB, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)
    cp = x_refs[0].shape[1]
    d2 = jnp.full((QB, cp), jnp.inf, jnp.float32)
    cand_i = jnp.full((QB, cp), -1, jnp.int32)
    # Row r's candidates come from its own tile: the whole block's distances
    # to that tile, of which row r is kept.
    for r in range(QB):
        x = x_refs[r][0].astype(jnp.float32)  # (Cp, Dp)
        if quantized:
            # per-member scales as a column, per-dimension offsets as a row
            x = x * scale_refs[r][0].T + offset_ref[...]
        xx = jnp.sum(x * x, axis=1)  # (Cp,)
        cross = jax.lax.dot_general(
            qv, x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )  # (QB, Cp)
        d2_r = jnp.maximum(qq + xx[None, :] - 2.0 * cross, 0.0)
        ids = ids_refs[r][0]  # (1, Cp)
        live = (ids >= 0) & (act_ref[(g * QB + r) * beam + b] > 0)
        mine = row == r
        d2 = jnp.where(mine, jnp.where(live, d2_r, jnp.inf), d2)
        cand_i = jnp.where(mine, jnp.where(live, ids, -1), cand_i)

    top_v, top_i, n = insert_topk(o_val_ref[...], o_idx_ref[...], d2, cand_i, kk)
    o_val_ref[...], o_idx_ref[...] = top_v, top_i
    o_ins_ref[...] += n  # (QB, 1) across the block's lanes


def _vmem_limit(cp: int, dp: int, kkp: int, itemsize: int, quantized: bool) -> int | None:
    """``vmem_limit_bytes`` for one call, or None where the default will do.

    The pipeline double-buffers every block: QB member tiles with their id
    (and scale) rows, the int8 variant's offset block (one 8-sublane f32
    tile), the query block, the four top-k blocks and the count block.  The
    body adds f32 working copies of a tile and, per row, a 128-lane column
    of squared norms.  In (Cp, 128) f32 columns, the v5e compiler asked for
    about 10 beyond the buffers at 128-d and 20 at 576-d (tiles of 1,024 to
    3,200 members); this counts 11 and 23, and a quarter on top.
    """
    rows = QB * cp * 4 * (2 if quantized else 1)
    blocks = QB * cp * dp * itemsize + rows + QB * dp * 4 + 4 * QB * kkp * 4 + QB * 128 * 4
    if quantized:
        blocks += QB * dp * 4  # the offset block
    body = 3 * cp * dp * 4 + QB * cp * 128 * 4
    limit = (2 * blocks + body) * 5 // 4
    return None if limit <= SCOPED_VMEM_BYTES else limit


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


def tile_bytes(steps: int, queries: int, beam: int, cap: int, dim: int, itemsize: int) -> int:
    """Member-tile bytes the compiled scan brings in over ``steps`` scan
    steps of ``queries`` queries: ceil(Q / 8) x beam programs a step, each
    8 tiles of Cp x Dp members of ``itemsize`` bytes, C and D padded as
    the compiled kernel pads them."""
    lane, cmult = _pad_multiples(False)
    cp, dp = -(-cap // cmult) * cmult, -(-dim // lane) * lane
    return steps * -(-queries // QB) * beam * QB * cp * dp * itemsize


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
    offset: Array | None = None,
    *,
    interpret: bool = False,
) -> tuple[Array, Array, Array | None, Array | None]:
    """Lay out the per-datastore operands for the kernel's blocks ONCE.

    Pads ``bucket_x`` to (NB, Cp, Dp) and turns ``bucket_ids``/``scale``
    into (NB, 1, Cp) rows.  An int8 store (``scale`` given) gets its (D,)
    ``offset`` as a (1, Dp) row, zero-padded, or zeros where the format
    has none.  ``bucket_scan_topk_pallas`` does the same on
    every call, as a no-op on operands prepared here; done inside a search
    while-loop it would copy the whole datastore each step, so callers that
    loop (core/knn.py) prepare at upload time.
    """
    lane, cmult = _pad_multiples(interpret)
    xp = _pad_to(_pad_to(bucket_x, 2, lane), 1, cmult)
    idsp = _member_rows(bucket_ids, cmult, -1)
    scalep = offsetp = None
    if scale is not None:
        scalep = _member_rows(scale.astype(jnp.float32), cmult, 0.0)
        if offset is None:
            offset = jnp.zeros((bucket_x.shape[2],), jnp.float32)
        offsetp = _pad_to(offset.astype(jnp.float32).reshape(1, -1), 1, lane)
    return xp, idsp, scalep, offsetp


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
    offset: Array | None = None,  # (D,) f32 with scale, or None: zero
    *,
    interpret: bool = False,
) -> tuple[Array, Array, Array]:
    """One fused scan step; returns the merged (top_d, top_i), both (Q, kk),
    and (Q,) i32: the candidates each query's top-k took in this step."""
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
                scale, offset, interpret=interpret,
            )
            for lo in range(0, qn, rows)
        ]
        return tuple(jnp.concatenate(p, axis=0) for p in zip(*parts))

    lane, _ = _pad_multiples(interpret)
    xp, idsp, scalep, offsetp = prepad_buckets(
        bucket_x, bucket_ids, scale, offset, interpret=interpret
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

    def member(r):
        # row r of the block reads the bucket it selected at this beam slot
        return lambda g, b, bsel, act: (bsel[(g * QB + r) * beam + b], 0, 0)

    def query_block(g, b, bsel, act):
        return (g, 0)

    def whole(g, b, bsel, act):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((QB, dp), query_block),
        *[pl.BlockSpec((1, cp, dp), member(r)) for r in range(QB)],
        *[pl.BlockSpec((1, 1, cp), member(r)) for r in range(QB)],
        *([pl.BlockSpec((1, 1, cp), member(r)) for r in range(QB)] if quantized else []),
        *([pl.BlockSpec((1, dp), whole)] if quantized else []),
        pl.BlockSpec((QB, kkp), query_block),
        pl.BlockSpec((QB, kkp), query_block),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # the output block depends on the first axis only: it stays
        # resident while the block merges its beam buckets in order
        grid=(qpn // QB, beam),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((QB, kkp), query_block),
            pl.BlockSpec((QB, kkp), query_block),
            pl.BlockSpec((QB, lane), query_block),
        ],
    )
    vals, idxs, ins = pl.pallas_call(
        functools.partial(_scan_kernel, kk=kk, beam=beam, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((qpn, kkp), jnp.float32),
            jax.ShapeDtypeStruct((qpn, kkp), jnp.int32),
            jax.ShapeDtypeStruct((qpn, lane), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(cp, dp, kkp, xp.dtype.itemsize, quantized)
        ),
        interpret=interpret,
    )(
        bsel_f,
        act_f,
        qp,
        *[xp] * QB,
        *[idsp] * QB,
        *([scalep] * QB + [offsetp] if quantized else []),
        top_dp,
        top_ip,
    )
    return vals[:qn, :kk], idxs[:qn, :kk], ins[:qn, 0]
