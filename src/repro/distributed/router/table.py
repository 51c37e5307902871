"""The replicated global routing table + the per-query host-pruning rule.

``build_routing_table`` runs on the HOST (numpy, f64 accumulation) against
the logical (unpadded, unquantized) forest at build/load/rebuild-swap time
and mirrors the executor's placement arithmetic exactly: bucket rows pad to
``ceil(NB/S)*S`` and shard ``s`` owns the contiguous slice
``[s*W, (s+1)*W)``; delta rows pad to ``ceil(I/S)*S`` likewise.  A table
built for the wrong shard count would silently mis-describe ownership, so
the backend rebuilds it whenever the forest or the shard count changes
(including the ``load(..., layout=...)`` host-count clamp).

``host_eligibility`` is the pure device-side pruning rule (DIMS-style
metric lower bounds, adapted to forest-mode selection):

  upper bound   Sort every *selected* region cover — per-(host, index)
                bucket covers ``d(q, c_i) + radius_hi[h, i]`` and per-index
                delta covers ``d(q, delta_pivot_i) + delta_radius_i`` — by
                ascending bound and take the bound at which the cumulative
                member count first reaches ``kk``: at least ``kk`` selected
                members lie within ``ub_sel``, so the merged kth-best
                distance cannot exceed it.  Fewer than ``kk`` selected
                members total -> ``+inf`` (nothing is pruned; the query's
                selection widens to every index).
  lower bound   Per host, the selection-INDEPENDENT floor over everything
                the host could ever contribute — ``d(q, host_center) -
                host_radius`` for its forest members and ``d(q,
                delta_pivot_i) - delta_radius_i`` over its owned non-empty
                delta rows (delta radii are dynamic, so they fold in here
                rather than being baked into the table).  Selection
                independence matters: an underfilled query's selection
                widens to every index, and those members must still be
                covered by the bound.

A host is pruned iff its lower bound strictly exceeds ``ub_sel`` plus a
small relative margin that absorbs f32 rounding; every candidate a pruned
host could produce then sits strictly beyond the merged kth-best, so
masking the host changes nothing — results stay bitwise identical
(tests/test_routed_exec.py gates this against fan-all and single-device).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# relative inflation applied to host-side covers before the f64 -> f32 cast:
# keeps every table radius a true upper bound after rounding (loosens
# pruning by ~1e-5, never tightens it)
_COVER_SLACK = 1e-5
# relative slack on the eligibility comparison itself — absorbs f32
# rounding in the device-side distance arithmetic (bounds are |q| + |r|
# magnitudes; 1e-4 is orders above f32 ulp noise)
_ELIG_MARGIN = 1e-4


class RoutingTable(NamedTuple):
    """Replicated per-host routing state (everything f32/i32, all small:
    O(S*I) — broadcast once, read by every query batch)."""

    host_centers: Array  # (S, D) f32 member-weighted pivot centroid per host
    host_radii: Array  # (S,) f32 cover of ALL owned forest members
    host_counts: Array  # (S,) i32 owned forest member counts
    radius_hi: Array  # (S, I) f32 cover of host s's index-i members around c_i
    count_hi: Array  # (S, I) i32 members of index i living on host s
    nbuckets_hi: Array  # (S, I) i32 non-empty buckets of index i on host s
    delta_owned: Array  # (S, I) bool: host s owns index i's delta buffer
    host_rates: Array  # (S, S) f32 registered overlap rates between regions


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def shard_owners(nb: int, shards: int) -> np.ndarray:
    """(NB,) owner shard per REAL bucket row under the executor's padding
    (rows pad to a shard multiple; shard s owns one contiguous slice)."""
    w = _ceil_to(max(nb, 1), shards) // shards
    return (np.arange(nb) // w).astype(np.int32)


def _conservative_f32(a: np.ndarray) -> np.ndarray:
    return ((1.0 + _COVER_SLACK) * a + _COVER_SLACK).astype(np.float32)


def _dequantized_members(xs: np.ndarray) -> np.ndarray:
    """Replicate kernels/ops.quantize_datastore's int8 round trip bitwise
    (same f32 IEEE ops, np.rint == jnp.round half-to-even): the positions
    a ``quantize=True`` scan actually measures distances to."""
    nb, cap, dim = xs.shape
    flat = xs.reshape(nb * cap, dim).astype(np.float32)
    scale = np.maximum(np.max(np.abs(flat), axis=1), 1e-8) / 127.0
    xq = np.clip(np.rint(flat / scale[:, None]), -127, 127)
    return (xq.astype(np.float32) * scale[:, None].astype(np.float32)).reshape(
        nb, cap, dim
    )


def build_routing_table(
    f, shards: int, *, method: str = "dbm", quantize: bool = False
) -> RoutingTable:
    """Host-side table build from the logical forest ``f`` (ForestArrays,
    f32 coordinates).  ``method`` resolves through the overlap-method
    registry, so VBM/DBM/OBM — or anything registered at runtime — rates
    the host regions; object-based methods see the real members with their
    owner-host assignment.

    ``quantize=True`` mirrors an int8 device layout: the scan measures
    distances to the DEQUANTIZED member positions, so every cover is
    recomputed around those (a true-member cover can undercut a quantized
    distance by up to a quantization step — far beyond the f32 margin —
    and silently prune a host that still holds a top-k candidate)."""
    from repro.core.overlap import get_overlap_method

    pivots = np.asarray(f.bucket_pivot, np.float64)  # (NB, D)
    radii = np.asarray(f.bucket_radius, np.float64)  # (NB,)
    mask = np.asarray(f.bucket_mask)  # (NB, C)
    bidx = np.asarray(f.bucket_index, np.int64)  # (NB,)
    centers = np.asarray(f.index_centers, np.float64)  # (I, D)
    nb, n_idx = pivots.shape[0], centers.shape[0]
    counts = mask.sum(axis=1).astype(np.int64)  # (NB,)
    owner = shard_owners(nb, shards)
    members = np.asarray(f.bucket_x, np.float32)  # (NB, C, D)
    if quantize:
        members = _dequantized_members(members)
        # per-bucket cover of the dequantized members around the pivot
        d_pm = np.linalg.norm(
            members.astype(np.float64) - pivots[:, None, :], axis=2
        )
        radii = np.where(mask, d_pm, 0.0).max(axis=1)

    host_centers = np.zeros((shards, pivots.shape[1]), np.float64)
    host_radii = np.zeros((shards,), np.float64)
    host_counts = np.zeros((shards,), np.int64)
    radius_hi = np.zeros((shards, n_idx), np.float64)
    count_hi = np.zeros((shards, n_idx), np.int64)
    nbuckets_hi = np.zeros((shards, n_idx), np.int64)
    # cover of index i's members around c_i, per bucket: d(c_i, pivot_b) + r_b
    d_cb = np.linalg.norm(
        centers[bidx.clip(0, n_idx - 1)] - pivots, axis=1
    ) + radii  # (NB,)
    for s in range(shards):
        rows = (owner == s) & (counts > 0)
        host_counts[s] = counts[rows].sum()
        if host_counts[s] == 0:
            continue
        host_centers[s] = (
            (pivots[rows] * counts[rows, None]).sum(axis=0) / host_counts[s]
        )
        host_radii[s] = (
            np.linalg.norm(pivots[rows] - host_centers[s], axis=1)
            + radii[rows]
        ).max()
        np.add.at(count_hi[s], bidx[rows], counts[rows])
        np.add.at(nbuckets_hi[s], bidx[rows], 1)
        np.maximum.at(radius_hi[s], bidx[rows], d_cb[rows])

    # delta-buffer ownership mirrors executor.place_delta's row padding
    wd = _ceil_to(max(n_idx, 1), shards) // shards
    delta_owned = (np.arange(n_idx) // wd)[None, :] == np.arange(shards)[:, None]

    entry = get_overlap_method(method)
    x_m = assign_m = None
    if entry.needs_objects:
        x_m = jnp.asarray(members[mask])
        assign_m = jnp.asarray(
            np.broadcast_to(owner[:, None], mask.shape)[mask]
        )
    rates = entry.matrix_fn(
        jnp.asarray(host_centers, jnp.float32),
        jnp.asarray(host_radii, jnp.float32),
        x=x_m,
        assign=assign_m,
    )

    return RoutingTable(
        host_centers=jnp.asarray(host_centers, jnp.float32),
        host_radii=jnp.asarray(_conservative_f32(host_radii)),
        host_counts=jnp.asarray(host_counts, jnp.int32),
        radius_hi=jnp.asarray(_conservative_f32(radius_hi)),
        count_hi=jnp.asarray(count_hi, jnp.int32),
        nbuckets_hi=jnp.asarray(nbuckets_hi, jnp.int32),
        delta_owned=jnp.asarray(delta_owned),
        host_rates=jnp.asarray(rates, jnp.float32),
    )


def host_eligibility(
    table: RoutingTable,
    d_center: Array,
    d_host: Array,
    sel: Array,
    kk: int,
    *,
    d_delta: Array | None = None,
    delta_radius: Array | None = None,
    delta_count: Array | None = None,
) -> tuple[Array, Array]:
    """(elig (Q, S) bool, ub_sel (Q,) f32) — the pruning rule.

    ``d_center`` (Q, I) and ``d_host`` (Q, S) are TRUE L2 distances to the
    index centers / host-region centers; ``sel`` (Q, I) is the same
    selection table the scan will use (pre host-masking).  The delta
    keywords carry the LIVE buffer state (pivot distances, radii, member
    counts for the logical I rows) — dynamic operands, never table state.
    """
    s_hosts, n_idx = table.count_hi.shape
    qn = d_center.shape[0]
    inf = jnp.float32(jnp.inf)

    # --- upper bound on the merged kth-best from SELECTED region covers ---
    valid_hi = sel[:, None, :] & (table.count_hi > 0)[None]  # (Q, S, I)
    vals = jnp.where(
        valid_hi, d_center[:, None, :] + table.radius_hi[None], inf
    ).reshape(qn, s_hosts * n_idx)
    cnts = jnp.where(valid_hi, table.count_hi[None], 0).reshape(
        qn, s_hosts * n_idx
    )
    if d_delta is not None:
        dvalid = sel & (delta_count > 0)[None]  # (Q, I)
        vals = jnp.concatenate(
            [vals, jnp.where(dvalid, d_delta + delta_radius[None], inf)],
            axis=1,
        )
        cnts = jnp.concatenate(
            [cnts, jnp.where(dvalid, delta_count[None], 0)], axis=1
        )
    order = jnp.argsort(vals, axis=1)
    vals_s = jnp.take_along_axis(vals, order, axis=1)
    cum = jnp.cumsum(jnp.take_along_axis(cnts, order, axis=1), axis=1)
    pos = jnp.argmax(cum >= kk, axis=1)
    filled = cum[:, -1] >= kk
    ub_sel = jnp.where(
        filled, jnp.take_along_axis(vals_s, pos[:, None], axis=1)[:, 0], inf
    )

    # --- per-host lower bound over EVERYTHING the host could contribute ---
    # Two valid covers of the host's forest members; take the tighter (max):
    #   * the single host ball (center + radius) — loose whenever contiguous
    #     row ownership straddles cluster boundaries (one far-away bucket
    #     inflates the ball over everything);
    #   * the per-(host, index) region covers — every owned member lies in
    #     some non-empty (h, i) region, so the min over regions of
    #     d(q, c_i) - radius_hi[h, i] lower-bounds all of them.
    lb_ball = jnp.where(
        (table.host_counts > 0)[None],
        jnp.maximum(d_host - table.host_radii[None], 0.0),
        inf,
    )  # (Q, S)
    lb_region = jnp.min(
        jnp.where(
            (table.count_hi > 0)[None],
            jnp.maximum(d_center[:, None, :] - table.radius_hi[None], 0.0),
            inf,
        ),
        axis=2,
    )  # (Q, S); +inf for empty hosts, matching lb_ball
    lb = jnp.maximum(lb_ball, lb_region)
    if d_delta is not None:
        lb_d_i = jnp.maximum(d_delta - delta_radius[None], 0.0)  # (Q, I)
        own_ne = table.delta_owned & (delta_count > 0)[None]  # (S, I)
        lb_d = jnp.min(
            jnp.where(own_ne[None], lb_d_i[:, None, :], inf), axis=2
        )  # (Q, S)
        lb = jnp.minimum(lb, lb_d)

    margin = _ELIG_MARGIN * (1.0 + jnp.where(jnp.isinf(ub_sel), 0.0, ub_sel))
    # empty hosts (lb == +inf) stay ineligible even when ub_sel == +inf —
    # they have nothing to contribute either way
    elig = (lb <= ub_sel[:, None] + margin[:, None]) & ~jnp.isinf(lb)
    return elig, ub_sel
