"""Streaming ingestion (IoT continuous-arrival path) — jittable inserts.

The paper builds its overlap-optimized forest once and serves it frozen; IoT
data never stops arriving.  This module adds the missing write path without
touching the frozen main structure: every index owns one fixed-capacity
**delta bucket** — a device-resident SoA tail array mirroring the forest's
bucket layout (coords / global ids / -1 padding) — and incoming batches are

  1. routed to their nearest index center (Alg. 2 STEP-1 routing, the same
     ``core.knn.route_points`` the query path uses),
  2. scatter-appended into that index's delta bucket (capacity-rejected
     points are reported back so the caller can trigger maintenance and
     retry — nothing is ever silently dropped),
  3. folded into per-index running sums (count / coordinate sum / max
     distance to the buffer pivot) so the maintenance monitor can recompute
     index centroids and conservative radius bounds WITHOUT touching the
     raw points again.

Search sees the buffers through ``core.knn.DeltaView`` (``delta_view``): one
extra bucket per index, scanned by the same fused Pallas bucket-scan kernel
as a second bounded phase and merged into the same top-k carry — forest +
delta search stays exact (tests/test_stream.py proves it against brute
force).  The buffer pivot is frozen at allocation (the owning index's
center), so the running ``radius`` is a valid lower-bound reference no
matter how many appends happen.

FITing-Tree's buffered-insert strategy (PAPERS.md) is the template: bounded
insert cost into a delta, bounded search degradation (one extra bucket per
selected index), periodic merge — here the merge trigger is the paper's own
overlap machinery (stream/maintenance.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import ForestArrays
from repro.core.knn import DeltaView, DeviceForest, route_points

Array = jax.Array


class DeltaBuffer(NamedTuple):
    """Per-index streaming append buffers + maintenance bookkeeping.

    The first five fields are the search-facing state (see ``delta_view``);
    the rest feed the overlap-drift monitor: ``sum_x``/``count`` give the
    delta centroid contribution, ``main_sum``/``main_count`` the frozen
    forest's contribution, so the *updated* index centroid is
    ``(main_sum + sum_x) / (main_count + count)`` with zero device scans.
    ``dropped`` counts capacity-rejected appends per index — any nonzero
    entry is a standing maintenance trigger.
    """

    x: Array  # (I, CAPD, D) f32 member coords, zero pad
    ids: Array  # (I, CAPD) i32 global object ids, -1 pad
    count: Array  # (I,) i32 live members per buffer
    pivot: Array  # (I, D) f32 frozen bound reference (index center at alloc)
    radius: Array  # (I,) f32 running max d(member, pivot)
    sum_x: Array  # (I, D) f32 running coordinate sum of delta members
    main_count: Array  # (I,) i32 member count of the frozen main forest
    main_sum: Array  # (I, D) f32 coordinate sum of the frozen main members
    main_radius: Array  # (I,) f32 frozen index radius (about ``pivot``)
    dropped: Array  # (I,) i32 capacity-rejected appends since alloc

    @property
    def capacity(self) -> int:
        return int(self.x.shape[1])


def main_index_sums(forest: ForestArrays) -> tuple[np.ndarray, np.ndarray]:
    """Per-index (member count, coordinate sum) of the frozen forest."""
    n_idx = forest.n_indexes
    dim = forest.bucket_x.shape[2]
    counts = np.zeros((n_idx,), np.int32)
    sums = np.zeros((n_idx, dim), np.float64)
    bcount = forest.bucket_mask.sum(axis=1)
    bsum = (forest.bucket_x * forest.bucket_mask[..., None]).sum(axis=1)
    np.add.at(counts, forest.bucket_index, bcount.astype(np.int32))
    np.add.at(sums, forest.bucket_index, bsum)
    return counts, sums.astype(np.float32)


def alloc_delta(forest: ForestArrays, capacity: int) -> DeltaBuffer:
    """Allocate empty delta buffers for every index of ``forest``."""
    n_idx = forest.n_indexes
    dim = forest.bucket_x.shape[2]
    main_count, main_sum = main_index_sums(forest)
    return DeltaBuffer(
        x=jnp.zeros((n_idx, capacity, dim), jnp.float32),
        ids=jnp.full((n_idx, capacity), -1, jnp.int32),
        count=jnp.zeros((n_idx,), jnp.int32),
        pivot=jnp.asarray(forest.index_centers, jnp.float32),
        radius=jnp.zeros((n_idx,), jnp.float32),
        sum_x=jnp.zeros((n_idx, dim), jnp.float32),
        main_count=jnp.asarray(main_count),
        main_sum=jnp.asarray(main_sum),
        main_radius=jnp.asarray(forest.index_radii, jnp.float32),
        dropped=jnp.zeros((n_idx,), jnp.int32),
    )


def delta_view(delta: DeltaBuffer) -> DeltaView:
    """Search-facing view (core.knn.DeltaView) of the append buffers."""
    mask = jnp.arange(delta.x.shape[1])[None, :] < delta.count[:, None]
    return DeltaView(
        x=delta.x, ids=delta.ids, mask=mask, pivot=delta.pivot, radius=delta.radius
    )


def append_routed(
    delta: DeltaBuffer, xb: Array, ids: Array, idx: Array, valid: Array
) -> tuple[DeltaBuffer, Array]:
    """Append one ALREADY-ROUTED batch; returns (new delta, accepted).

    ``idx`` (B,) i32 is the destination buffer row; any value >= the row
    count parks the point (every scatter drops the row, it consumes no slot,
    counts nowhere — not even ``dropped`` — and reports accepted=False when
    ``valid`` is also False).  ``valid`` (B,) bool marks the rows that are
    really in the batch this round.

    This is the executor body both device layouts share: the single-device
    path calls it with GLOBAL buffer rows, the sharded island per shard with
    LOCAL rows (other shards' points arrive parked).  Slot assignment sorts
    by destination and ranks within runs (O(B log B), no (B, B) mask) — a
    stable sort preserves batch order within each destination run, so the
    per-index slot layout is bitwise-identical across layouts.  Pure and
    un-jitted; callers own the compilation boundary.
    """
    b = xb.shape[0]
    n_idx = delta.count.shape[0]
    capd = delta.x.shape[1]

    # 1. slot assignment: rank within same-destination runs of the batch
    order = jnp.argsort(idx, stable=True)
    s = idx[order]  # (B,) sorted destinations
    pos = jnp.arange(b, dtype=jnp.int32)
    run_start = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    start_pos = jax.lax.cummax(jnp.where(run_start, pos, 0))
    rank = pos - start_pos  # position within the run
    slot = delta.count[s] + rank  # (B,) target slot in sorted order
    acc_sorted = slot < capd

    # 2. scatter-append (out-of-capacity slots drop out of the scatter)
    xs = xb[order]
    new_x = delta.x.at[s, slot].set(xs, mode="drop")
    new_ids = delta.ids.at[s, slot].set(ids[order], mode="drop")

    # unsort the accept mask back to batch order
    accepted = jnp.zeros((b,), bool).at[order].set(acc_sorted)
    accepted = accepted & valid  # parked rows: slot math is meaningless

    # 3. running bookkeeping (accepted points only; parked rows scatter to
    #    the out-of-range virtual index and drop)
    d_piv = jnp.sqrt(
        jnp.maximum(
            jnp.sum((xb - delta.pivot[jnp.minimum(idx, n_idx - 1)]) ** 2, axis=-1),
            0.0,
        )
    )  # (B,) distance to the frozen buffer pivot
    one = accepted.astype(jnp.int32)
    new_count = delta.count.at[idx].add(one, mode="drop")
    new_radius = delta.radius.at[idx].max(
        jnp.where(accepted, d_piv, -jnp.inf), mode="drop"
    )
    new_sum = delta.sum_x.at[idx].add(
        jnp.where(accepted[:, None], xb, 0.0), mode="drop"
    )
    new_dropped = delta.dropped.at[idx].add(1 - one, mode="drop")

    return (
        delta._replace(
            x=new_x, ids=new_ids, count=new_count, radius=new_radius,
            sum_x=new_sum, dropped=new_dropped,
        ),
        accepted,
    )


def ingest_impl(
    centers: Array,
    delta: DeltaBuffer,
    xb: Array,
    ids: Array,
    valid: Array | None = None,
) -> tuple[DeltaBuffer, Array]:
    """Route + append one batch (un-jitted executor body; see ``ingest``).

    Takes the routing CENTERS, not the whole ``DeviceForest``: ingest never
    reads the bucket arrays, and a maintenance rebuild changes their shapes
    — keying the jit cache on the full forest forced a full re-trace after
    every rebuild (the BENCH_stream ~360 points/s regression).  Centers keep
    a stable (I, D) shape for the life of the index.
    """
    n_idx = delta.count.shape[0]
    xb = xb.astype(jnp.float32)
    ids = ids.astype(jnp.int32)

    # route (STEP-1; same arithmetic as the query path)
    _, idx = route_points(centers, xb)  # (B,)
    if valid is None:
        valid = jnp.ones((xb.shape[0],), bool)
    else:
        idx = jnp.where(valid, idx, n_idx)  # park: every scatter drops row I
    return append_routed(delta, xb, ids, idx, valid)


_ingest_jit = jax.jit(ingest_impl)


def ingest(
    forest: DeviceForest,
    delta: DeltaBuffer,
    xb: Array,
    ids: Array,
    valid: Array | None = None,
) -> tuple[DeltaBuffer, Array]:
    """Route + append one batch; returns (new delta, accepted (B,) bool).

    Jitted end to end (cache keyed on the routing centers + operand shapes,
    NOT the forest's bucket arrays): routing reuses STEP-1
    (``route_points``), slot assignment sorts the batch by destination index
    and ranks within runs (O(B log B), no (B, B) mask), appends are a single
    scatter with ``mode='drop'`` — a slot past capacity falls outside the
    array and the point is reported rejected instead of written.

    ``accepted[j]`` is False only when point j's destination buffer is full;
    the caller requeues those points after running maintenance (see
    api.OverlapIndex.ingest, which never loses a point).

    ``valid`` (optional (B,) bool) masks rows out of the batch entirely:
    invalid rows are parked on a virtual out-of-range index so they consume
    no slots, store nothing, count nowhere (not even ``dropped``), and
    report accepted=False.  Retry loops keep the SAME batch shape across
    rounds by flipping the mask instead of slicing — one compiled program
    instead of one per rejected-point count.
    """
    return _ingest_jit(forest.index_centers, delta, xb, ids, valid)


def updated_geometry(delta: DeltaBuffer) -> tuple[Array, Array]:
    """Post-ingest index geometry from the running sums — no member scans.

    Returns (centers (I, D), radius upper bounds (I,)).  The center is the
    exact centroid of main + delta members.  The radius is a conservative
    upper bound: every member lies within ``max(r_main, r_delta)`` of the
    OLD center (main members by construction, delta members by the running
    max), so it lies within that plus the center shift of the NEW center.
    Conservative is the right direction for the drift monitor — overlap
    rates computed from upper-bound radii can only over-trigger, never miss
    a genuinely overlapping pair.
    """
    total = jnp.maximum(delta.main_count + delta.count, 1)
    centers = (delta.main_sum + delta.sum_x) / total[:, None].astype(jnp.float32)
    shift = jnp.sqrt(
        jnp.maximum(jnp.sum((centers - delta.pivot) ** 2, axis=-1), 0.0)
    )
    return centers, jnp.maximum(delta.main_radius, delta.radius) + shift


def ingest_host(
    forest: DeviceForest, delta: DeltaBuffer, xb: np.ndarray, ids: np.ndarray
) -> tuple[DeltaBuffer, np.ndarray]:
    """Host convenience wrapper around ``ingest``."""
    nd, acc = ingest(forest, delta, jnp.asarray(xb, jnp.float32), jnp.asarray(ids))
    return nd, np.asarray(acc)


def pull_delta_meta(delta: DeltaBuffer, *, ids: bool = False) -> dict[str, np.ndarray]:
    """Device -> host snapshot of the buffer METADATA (maintenance reads
    this).  Deliberately excludes the (I, CAPD, D) coordinate block — no
    consumer needs it on the host (rebuilds fetch rows from the caller's
    accumulated dataset by global id), and the drift monitor runs per batch,
    so copying megabytes of coordinates every check would dominate its cost.
    ``ids=True`` adds the (I, CAPD) id table (OBM assignment + rebuilds)."""
    out = {
        "count": np.asarray(delta.count),
        "radius": np.asarray(delta.radius),
        "sum_x": np.asarray(delta.sum_x),
        "main_count": np.asarray(delta.main_count),
        "main_sum": np.asarray(delta.main_sum),
        "main_radius": np.asarray(delta.main_radius),
        "dropped": np.asarray(delta.dropped),
    }
    if ids:
        out["ids"] = np.asarray(delta.ids)
    return out


def route_batch_host(forest: DeviceForest, xb: np.ndarray) -> np.ndarray:
    """Host helper: destination index per point (routing only, no append)."""
    _, idx = route_points(forest.index_centers, jnp.asarray(xb, jnp.float32))
    return np.asarray(idx)
