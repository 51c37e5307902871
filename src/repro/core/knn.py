"""k-NN search over the flattened forest (paper Algorithm 2) — jittable.

Paper Alg. 2:  STEP 1 route the query to the closest index center and append
that index's neighbor overlap-indexes; STEP 2 run the kNN-BCCF
branch-and-bound on every selected index in parallel; STEP 3 gather.

TPU-native realization (DESIGN.md §3): the per-index branch-and-bound descent
becomes a *sorted-lower-bound masked bucket scan* over the forest's flattened
buckets:

  1. route:   d(q, index_centers) -> closest + neighbors -> eligibility mask
              over buckets (STEP 1; identical selection semantics).
  2. bound:   lb_b = max(0, d(q, bucket_pivot_b) - bucket_radius_b) for all
              eligible buckets (one distance-matrix kernel), +inf elsewhere.
  3. scan:    visit buckets in ascending-lb order under a ``lax.while_loop``;
              each step evaluates the next ``beam`` buckets per query
              (distance block + top-k merge) and stops once
              lb > kth-best for every query (exact termination: lb is sorted
              and kth-best is non-increasing).

The scan visits a superset-free ordering of what best-first tree descent
visits, so the paper's cost metrics (distance computations, bucket/node
accesses, comparisons) are preserved and instrumented per query.  The first
visited bucket doubles as the paper's Estimated-r_q seed (kth distance of the
nearest leaf).

``mode='all'`` disables routing (every index selected) — used by tests to
prove the scan is EXACT against brute force, and by callers who want exact
global kNN at higher cost.

Streaming deltas (repro.stream): ``knn_search_impl(..., delta=DeltaView)``
runs a SECOND bounded scan phase over the per-index delta tail buckets (the
device-resident append buffers of stream/ingest.py), seeded with the main
phase's top-k carry.  The delta buckets behave exactly like forest buckets
(pivot/radius lower bounds, same fused kernel step); because lower bounds are
only ever pruning conditions, splitting the scan into two phases preserves
exactness — the main phase merely prunes against a k-th best that ignores
delta members (visits a superset), and the delta phase prunes against the
true running k-th best.

Under-filled selections: when the selected indexes (forest members plus
delta members) hold fewer than k objects, the query's selection widens to
every index (``widen_underfilled``), so it gets the exact global answer —
the paper's §4.3 intent of searching on "particularly when the required
number of objects has not yet been reached".  The scan visits only buckets
with a finite lower bound, i.e. selected ones.  Both rules read the
GLOBAL selection, so a sharded executor, whose shards may hold few or
none of a query's selected buckets, visits what the single-device one
visits and returns the same answer.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.forest import ForestArrays
from repro.core.metric import pairwise
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Array = jax.Array


class DeviceForest(NamedTuple):
    index_centers: Array  # (I, D)
    index_radii: Array  # (I,)
    neighbors: Array  # (I, MAXNBR) i32, -1 pad
    bucket_x: Array  # (NB, C, D) f32, or int8 when quantized
    bucket_ids: Array  # (NB, C) i32, -1 pad
    bucket_mask: Array  # (NB, C) bool
    bucket_pivot: Array  # (NB, D) f32 (bounds stay full precision)
    bucket_radius: Array  # (NB,)
    bucket_index: Array  # (NB,) i32
    bucket_scale: Array | None = None  # (NB, C) f32 dequant scales (int8 mode)


class DeltaView(NamedTuple):
    """Search-facing view of the streaming delta buffers (repro.stream).

    One delta bucket per index: fixed-capacity tail arrays appended to by
    stream/ingest.ingest.  ``pivot`` is the reference point the running
    ``radius`` bound is maintained against (the owning index's center at
    buffer allocation), so ``max(0, d(q, pivot) - radius)`` is a valid lower
    bound on any member distance.  Unfilled slots carry id -1 (the same
    padding contract as ``DeviceForest.bucket_ids``)."""

    x: Array  # (I, CAPD, D) f32
    ids: Array  # (I, CAPD) i32, -1 pad
    mask: Array  # (I, CAPD) bool
    pivot: Array  # (I, D) f32
    radius: Array  # (I,) f32


class SearchStats(NamedTuple):
    buckets_visited: Array  # (Q,) i32
    distances: Array  # (Q,) i32  useful (unpadded) OBJECT distances
    bound_distances: Array  # (Q,) i32  routing (centers) + bucket-bound dists
    padded_distances: Array  # (Q,) i32  object distances incl. padding lanes
    comparisons: Array  # (Q,) i32  routing + bound + top-k comparisons
    steps: Array  # () i32  while-loop trip count
    topk_inserts: Array  # (Q,) i32  candidates that entered the running top-k


class IslandStats(NamedTuple):
    """Per-executor-island node-access counters (leading dim = islands).

    The paper's cost currency — bucket/node accesses and bound distance
    evaluations — broken down by WHICH executor island did the work: one
    row per shard under the sharded layout (each shard scans its local
    bucket rows, so the rows expose load balance), a single row on the
    single-device layout.  ``SearchStats`` stays the fleet total; this is
    the telemetry layer's per-island view (``OverlapIndex.metrics()``).
    """

    buckets_visited: Array  # (S, Q) i32 per-shard bucket visits
    distances: Array  # (S, Q) i32 per-shard useful object distances
    bound_distances: Array  # (S, Q) i32 per-shard routing + bound distances


def device_forest(f: ForestArrays, *, quantize: bool = False) -> DeviceForest:
    """Upload the flattened forest; ``quantize=True`` stores bucket members
    int8 with per-member scales (kernels/ops.quantize_datastore layout) —
    4x less HBM traffic on the member scan; bounds/pivots stay f32."""
    bucket_x = jnp.asarray(f.bucket_x)
    bucket_scale = None
    if quantize:
        nb, cap, dim = bucket_x.shape
        xq, scale = kops.quantize_datastore(bucket_x.reshape(nb * cap, dim))
        bucket_x = xq.reshape(nb, cap, dim)
        bucket_scale = scale.reshape(nb, cap)
    return DeviceForest(
        index_centers=jnp.asarray(f.index_centers),
        index_radii=jnp.asarray(f.index_radii),
        neighbors=jnp.asarray(f.neighbors),
        bucket_x=bucket_x,
        bucket_ids=jnp.asarray(f.bucket_ids),
        bucket_mask=jnp.asarray(f.bucket_mask),
        bucket_pivot=jnp.asarray(f.bucket_pivot),
        bucket_radius=jnp.asarray(f.bucket_radius),
        bucket_index=jnp.asarray(f.bucket_index),
        bucket_scale=bucket_scale,
    )


def route_points(centers: Array, q: Array) -> tuple[Array, Array]:
    """Alg. 2 STEP 1 routing: distances to index centers + closest index.

    Shared by the query path (knn_search_impl) and the streaming ingest router
    (stream/ingest.ingest) — both assign a point to its nearest index center.
    Returns (d_idx (Q, I) squared distances, closest (Q,) i32).
    """
    d_idx = pairwise(q, centers, metric="sq_l2")  # (Q, I)
    return d_idx, jnp.argmin(d_idx, axis=1).astype(jnp.int32)


def route_eligibility(closest: Array, neighbors: Array) -> Array:
    """(Q, I) bool: closest index + its overlap-index neighbors, per query.

    Scatter formulation via ``segment_max``: each query contributes
    1 + MAXNBR (query, index) pairs; one segment per (query, index) cell.
    Replaces the (Q, I, MAXNBR) one-hot mask product — the one-hot path
    materialized O(Q * I * MAXNBR) work for what is O(Q * MAXNBR) pairs,
    which matters for forests with many indexes (ROADMAP item).
    """
    n_idx = neighbors.shape[0]
    qn = closest.shape[0]
    nbrs = neighbors[closest]  # (Q, MAXNBR)
    cand = jnp.concatenate(
        [closest[:, None], jnp.where(nbrs >= 0, nbrs, 0)], axis=1
    )  # (Q, 1 + MAXNBR), invalid links parked on index 0 with value 0
    val = jnp.concatenate(
        [jnp.ones((qn, 1), jnp.int32), (nbrs >= 0).astype(jnp.int32)], axis=1
    )
    seg = (cand.astype(jnp.int32) + n_idx * jnp.arange(qn, dtype=jnp.int32)[:, None]).ravel()
    sel = jax.ops.segment_max(val.ravel(), seg, num_segments=qn * n_idx)
    return sel.reshape(qn, n_idx) > 0


class _Carry(NamedTuple):
    top_d: Array  # (Q, kk) ascending squared dists
    top_i: Array  # (Q, kk) ids
    t: Array
    visits: Array
    ndist: Array
    npad: Array
    inserts: Array


class ScanOut(NamedTuple):
    """One executor's bounded-scan result BEFORE the stats rollup: the top-k
    carry plus the raw per-query cost counters.  On the single-device path
    this is the whole search; on the sharded path each shard produces one
    and the island merges ``top_d``/``top_i`` (``merge_shard_topk``) and
    ``psum``s the counters before ``scan_stats`` builds ``SearchStats``."""

    top_d: Array  # (Q, kk) ascending SQUARED distances
    top_i: Array  # (Q, kk) global object ids, -1 pad
    visits: Array  # (Q,) i32
    ndist: Array  # (Q,) i32
    npad: Array  # (Q,) i32
    steps: Array  # () i32
    n_elig: Array  # (Q,) i32 eligible main buckets
    n_elig_d: Array  # (Q,) i32 eligible delta buckets
    inserts: Array  # (Q,) i32 candidates that entered the top-k carry
    # main-phase-only visit counts (visits - visits_main = delta visits);
    # the attribution layer decodes visited rows from this + the sorted
    # visit order.  Appended with a default so positional/keyword
    # constructions that predate it stay valid; dead in a search plan
    # (DCE'd out of compiled executors that don't return VisitRows).
    visits_main: Array | None = None


def _sorted_bounds(lb: Array, beam: int) -> tuple[Array, Array, Array]:
    """Ascending visit order + sorted bounds, padded to a beam multiple."""
    nb = lb.shape[1]
    order = jnp.argsort(lb, axis=1)
    lb_sorted = jnp.take_along_axis(lb, order, axis=1)
    n_steps = -(-nb // beam)  # ceil
    pad = n_steps * beam - nb
    if pad:
        order = jnp.pad(order, ((0, 0), (0, pad)))
        lb_sorted = jnp.pad(lb_sorted, ((0, 0), (0, pad)), constant_values=jnp.inf)
    return order, lb_sorted, jnp.int32(n_steps)


def _scan_phase(
    carry: _Carry,
    q: Array,
    order: Array,
    lb_sorted: Array,
    n_steps: Array,
    beam: int,
    scan_step,
    scan_x: Array,
    scan_ids: Array,
    scan_scale: Array | None,
    bucket_count: Array,
    cap: int,
    qmask: Array | None = None,
) -> _Carry:
    """One bounded best-first scan phase (main buckets or delta buckets).

    Visits buckets in ascending-lb order until lb > kth-best for every query
    (exact termination: lb is sorted and kth-best is non-increasing).  The
    carry's top-k streams THROUGH phases: the delta phase starts from the
    main phase's result and keeps merging into the same (Q, kk) state.

    ``qmask`` (Q,) bool — optional per-query kill switch: a False query
    visits NOTHING in this phase.  The routed layout uses it to turn a
    pruned (query, host) pair into genuine zero work on that host;
    ``None`` (every other caller) compiles to the unmasked predicate.
    """

    def active_mask(c: _Carry) -> Array:
        kth = jnp.sqrt(c.top_d[:, -1])  # inf until kk found
        cur_lb = jax.lax.dynamic_slice_in_dim(lb_sorted, c.t * beam, beam, axis=1)
        # +inf bounds are unselected rows: never visited, even while the
        # carry is short of kk (``widen_underfilled`` owns that case)
        act = (cur_lb <= kth[:, None]) & jnp.isfinite(cur_lb)  # (Q, beam)
        if qmask is not None:
            act = act & qmask[:, None]
        return act

    def cond(c: _Carry) -> Array:
        return (c.t < n_steps) & jnp.any(active_mask(c))

    def body(c: _Carry) -> _Carry:
        act = active_mask(c)  # (Q, beam)
        bsel = jax.lax.dynamic_slice_in_dim(order, c.t * beam, beam, axis=1)
        # fused gather -> squared-L2 -> running top-k merge (one kernel step;
        # the (Q, beam, C, D) gather never materializes on the kernel path)
        new_d, new_i, n_ins = scan_step(
            q, scan_x, scan_ids, bsel, act, c.top_d, c.top_i, scan_scale
        )
        n_members = jnp.where(act, bucket_count[bsel], 0)  # (Q, beam)
        return _Carry(
            top_d=new_d,
            top_i=new_i,
            t=c.t + 1,
            visits=c.visits + jnp.sum(act, axis=1, dtype=jnp.int32),
            ndist=c.ndist + jnp.sum(n_members, axis=1, dtype=jnp.int32),
            npad=c.npad + jnp.sum(act, axis=1, dtype=jnp.int32) * cap,
            inserts=c.inserts + n_ins,
        )

    return jax.lax.while_loop(cond, body, carry)


def route_select(
    forest: DeviceForest, q: Array, *, mode: str = "forest"
) -> tuple[Array, Array, Array]:
    """Alg. 2 STEP 1: per-query index selection + the routing cost counters.

    Returns (sel (Q, I) bool, route_dists (Q,) i32, route_cmps (Q,) i32).
    Touches only the REPLICATED forest leaves (centers, neighbors), so the
    sharded island runs it unchanged on every shard — identical selection
    everywhere is what makes the per-shard scans exact.
    """
    qn = q.shape[0]
    n_idx = forest.index_centers.shape[0]
    if mode == "forest":
        _, closest = route_points(forest.index_centers, q)
        sel = route_eligibility(closest, forest.neighbors)  # (Q, I)
        route_dists = jnp.full((qn,), n_idx, jnp.int32)
        route_cmps = jnp.full((qn,), n_idx, jnp.int32)
    elif mode == "all":
        sel = jnp.ones((qn, n_idx), jnp.bool_)
        route_dists = jnp.zeros((qn,), jnp.int32)
        route_cmps = jnp.zeros((qn,), jnp.int32)
    else:
        raise ValueError(f"mode {mode!r}")
    return sel, route_dists, route_cmps


def index_members(forest: DeviceForest, delta: DeltaView | None = None) -> Array:
    """(I,) i32 live objects per index over the bucket rows (and delta
    rows, one per index) this executor holds.  Rows owned by the sharded
    layout's sentinel index I (alignment padding) count nowhere."""
    n_idx = forest.index_centers.shape[0]
    per_bucket = jnp.sum(forest.bucket_mask, axis=1, dtype=jnp.int32)
    counts = jax.ops.segment_sum(
        per_bucket, forest.bucket_index, num_segments=n_idx + 1
    )[:n_idx]
    if delta is not None:
        counts = counts + jnp.sum(delta.mask, axis=1, dtype=jnp.int32)
    return counts


def widen_underfilled(sel: Array, members: Array, kk: int) -> Array:
    """(Q, I) selection with every index selected for the queries whose
    selection holds fewer than ``kk`` of the ``members`` (I,) objects."""
    held = jnp.sum(jnp.where(sel, members[None, :], 0), axis=1)
    return sel | (held < kk)[:, None]


class PhaseBounds(NamedTuple):
    """STEP 2a output for one scan phase: the ascending visit order, the
    sorted lower bounds (ineligible rows at +inf, padded to a beam multiple)
    and the per-query eligible-row count for the cost instrumentation."""

    order: Array  # (Q, n_steps*beam) int
    lb_sorted: Array  # (Q, n_steps*beam) f32, ascending, +inf tail
    n_elig: Array  # (Q,) i32


def bucket_bounds(
    forest: DeviceForest,
    q: Array,
    bucket_sel: Array,
    *,
    beam: int = 1,
) -> PhaseBounds:
    """STEP 2a over the main bucket rows: eligibility -> pivot lower bounds
    -> sorted visit order.

    Split from the scan body because the SORT must not share a program
    region with the scan's ``while_loop`` under ``shard_map``+``jit`` (the
    SPMD partitioner miscompiles sort-feeds-while on manually sharded
    operands; see ``distributed/knn_island.sharded_search``).  The
    single-device path simply calls both stages back to back — identical
    ops, identical results.  ``bucket_sel`` (Q, I') is the selection table
    indexed by ``forest.bucket_index``; I' may exceed the true index count
    so that the sharded layout's pad buckets can point at an always-False
    sentinel column.
    """
    elig = bucket_sel[:, forest.bucket_index]  # (Q, NB) -> sel[q, owner(b)]
    # Bounds are only *used* for eligible buckets (ineligible ones are masked
    # to +inf below), so the paper's Fig. 21 cost metric charges exactly the
    # eligible count per query — not all NB rows of the distance matrix.
    n_elig = jnp.sum(elig, axis=1, dtype=jnp.int32)  # (Q,)
    d_piv = pairwise(q, forest.bucket_pivot, metric="l2")  # (Q, NB)
    lb = jnp.maximum(d_piv - forest.bucket_radius[None, :], 0.0)
    lb = jnp.where(elig, lb, jnp.inf)
    order, lb_sorted, _ = _sorted_bounds(lb, beam)
    return PhaseBounds(order=order, lb_sorted=lb_sorted, n_elig=n_elig)


def delta_bounds(
    delta: DeltaView,
    q: Array,
    delta_sel: Array,
    *,
    beam: int = 1,
) -> PhaseBounds:
    """STEP 2a over the delta rows (one streaming bucket per index; empty
    buffers are never eligible)."""
    dcount = jnp.sum(delta.mask, axis=1, dtype=jnp.int32)  # (I_d,)
    elig_d = delta_sel & (dcount[None, :] > 0)  # (Q, I_d)
    n_elig_d = jnp.sum(elig_d, axis=1, dtype=jnp.int32)
    d_piv_d = pairwise(q, delta.pivot, metric="l2")
    lb_d = jnp.maximum(d_piv_d - delta.radius[None, :], 0.0)
    lb_d = jnp.where(elig_d, lb_d, jnp.inf)
    order_d, lb_d_sorted, _ = _sorted_bounds(lb_d, beam)
    return PhaseBounds(order=order_d, lb_sorted=lb_d_sorted, n_elig=n_elig_d)


def scan_sorted(
    forest: DeviceForest,
    q: Array,
    bounds: PhaseBounds,
    *,
    kk: int,
    beam: int = 1,
    delta: DeltaView | None = None,
    dbounds: PhaseBounds | None = None,
    qmask: Array | None = None,
) -> ScanOut:
    """STEP 2b/2c executor body: bounded best-first scan over the bucket
    rows (and delta rows) it is given, visiting in the precomputed
    ``PhaseBounds`` order.  Contains the ``while_loop`` but NO sort — see
    ``bucket_bounds`` for why the stages are split.  ``qmask`` (Q,) bool
    suppresses both phases per query (see ``_scan_phase``; the routing
    tier's host pruning)."""
    qn = q.shape[0]
    _, cap, _ = forest.bucket_x.shape

    init = _Carry(
        top_d=jnp.full((qn, kk), jnp.inf),
        top_i=jnp.full((qn, kk), -1, jnp.int32),
        t=jnp.int32(0),
        visits=jnp.zeros((qn,), jnp.int32),
        ndist=jnp.zeros((qn,), jnp.int32),
        npad=jnp.zeros((qn,), jnp.int32),
        inserts=jnp.zeros((qn,), jnp.int32),
    )

    # real (unpadded) member count per bucket, for the cost instrumentation
    bucket_count = jnp.sum(forest.bucket_mask, axis=1, dtype=jnp.int32)  # (NB,)
    # tile-align the datastore-sized operands ONCE, outside the loop — the
    # kernel wrapper's defensive per-call pads become no-ops
    scan_x, scan_ids, scan_scale = kops.bucket_scan_prepad(
        forest.bucket_x, forest.bucket_ids, forest.bucket_scale
    )

    # order/lb_sorted are padded to exactly n_steps*beam (``_sorted_bounds``)
    n_steps = jnp.int32(bounds.order.shape[1] // beam)
    out = _scan_phase(
        init, q, bounds.order, bounds.lb_sorted, n_steps, beam,
        kops.bucket_scan_topk, scan_x, scan_ids, scan_scale, bucket_count, cap,
        qmask=qmask,
    )
    total_steps = out.t
    visits_main = out.visits

    n_elig_d = jnp.zeros((qn,), jnp.int32)
    if delta is not None:
        dcap = delta.x.shape[1]
        dcount = jnp.sum(delta.mask, axis=1, dtype=jnp.int32)  # (I_d,)
        dx, dids, _ = kops.bucket_scan_prepad(delta.x, delta.ids, None)
        n_steps_d = jnp.int32(dbounds.order.shape[1] // beam)
        out = _scan_phase(
            out._replace(t=jnp.int32(0)), q, dbounds.order, dbounds.lb_sorted,
            n_steps_d, beam, kops.delta_scan_topk, dx, dids, None, dcount, dcap,
            qmask=qmask,
        )
        total_steps = total_steps + out.t
        n_elig_d = dbounds.n_elig

    return ScanOut(
        top_d=out.top_d,
        top_i=out.top_i,
        visits=out.visits,
        ndist=out.ndist,
        npad=out.npad,
        steps=total_steps,
        n_elig=bounds.n_elig,
        n_elig_d=n_elig_d,
        inserts=out.inserts,
        visits_main=visits_main,
    )


def merge_shard_topk(
    top_d: Array, top_i: Array, *, k: int, axis_name: str
) -> tuple[Array, Array]:
    """Cross-shard top-k merge: gather k candidates per shard, keep the
    global k.  Exactly the flat-datastore merge ``serve/retrieval.knn_logits``
    runs — collective volume is k * 2 scalars per query per shard, never the
    datastore.  k-per-shard guarantees exactness: the global top-k is a
    subset of the union of per-shard top-ks.
    """
    d_all = jax.lax.all_gather(top_d, axis_name, axis=1, tiled=True)  # (Q, S*k)
    i_all = jax.lax.all_gather(top_i, axis_name, axis=1, tiled=True)
    return kref.topk_by_distance_then_id(d_all, i_all, k)


def scan_stats(
    route_dists: Array, route_cmps: Array, out: ScanOut, *, kk: int
) -> SearchStats:
    """Roll a (possibly merged) ``ScanOut`` + routing counters into the
    paper's ``SearchStats``.  Shared by both executors so the instrumented
    cost model cannot drift between layouts."""
    return SearchStats(
        buckets_visited=out.visits,
        distances=out.ndist,
        bound_distances=route_dists + out.n_elig + out.n_elig_d,
        padded_distances=out.npad,
        comparisons=route_cmps
        + out.n_elig + out.n_elig_d  # bound comparisons (eligible buckets)
        # top-k merge comparisons over every padded lane actually scanned
        # (npad carries each phase's own bucket capacity)
        + out.npad * jnp.int32(int(np.ceil(np.log2(max(kk, 2))))),
        steps=out.steps,
        topk_inserts=out.inserts,
    )


class VisitRows(NamedTuple):
    """Per-query visited-row evidence for the attribution layer
    (``obs/attribution.py``) — one uniform layout across device layouts.

    Exactness of the decode rests on a scan invariant: within one executor
    (one shard, one phase) the visited buckets are EXACTLY the prefix of
    the ascending-lower-bound visit order of length ``visits[s, q]`` — the
    scan walks ``order`` front to back and the termination predicate
    (``lb_sorted <= kth_best``) can only flip from visit to skip, never
    back, because ``lb_sorted`` ascends while kth-best is non-increasing.
    So (order, per-phase visit counts) reconstructs the visited set
    host-side without re-running anything.

    ``order`` concatenates the S per-shard LOCAL sorted orders along axis 1
    (block s spans columns ``[s*W, (s+1)*W)`` with ``W = order.shape[1] //
    S``; entries are SHARD-LOCAL row ids — global row = local + s *
    rows_per_shard).  The single layout is the S=1 special case where
    local == global.  ``dorder``/``dvisits`` are the delta phase's twin
    (``None`` when no delta phase was compiled in).
    """

    order: Array  # (Q, S*W) per-shard-local sorted visit orders, col-stacked
    visits: Array  # (S, Q) i32 MAIN-phase visited counts per shard
    dorder: Array | None  # (Q, S*Wd) delta visit orders
    dvisits: Array | None  # (S, Q) i32 delta-phase visited counts per shard


def knn_search_impl(
    forest: DeviceForest,
    q: Array,
    *,
    k: int,
    mode: str = "forest",
    beam: int = 1,
    delta: DeltaView | None = None,
) -> tuple[Array, Array, SearchStats, VisitRows]:
    """Batched kNN over the forest.

    Returns (dists (Q,k), ids (Q,k), SearchStats, VisitRows).  dists are
    true L2 distances; ids are global object ids (-1 if fewer than k
    objects were reachable).  ``VisitRows`` is the visited-row evidence the
    attribution layer decodes: arrays the scan computes along the way, so a
    caller that drops it (inside ``jax.jit``) compiles none of it.

    This is the EXECUTOR: a pure, un-jitted function.  The facade's planner
    (``repro.api.plan.SearchPlan``) closes a ``jax.jit`` over it once per
    static-option tuple ``(k, mode, beam, quantize, delta shape)`` and
    caches the compiled executable so repeated searches with stable shapes
    never re-trace.  Every distance — STEP 1 routing, STEP 2a bucket bounds
    and the STEP 2b fused gather+distance+top-k bucket scan — goes through
    the ``repro.kernels.ops`` dispatch layer, which alone picks the
    implementation (compiled Pallas on TPU, interpret mode under
    REPRO_FORCE_PALLAS=1, the jnp reference elsewhere).

    ``delta`` (a DeltaView) adds the streaming delta buckets as a second scan
    phase: the same bounded best-first scan, seeded with the main phase's
    top-k carry, over the per-index append buffers.  Results are then exact
    over main forest + delta members (within the mode's selection semantics).
    """
    n_idx = forest.index_centers.shape[0]
    nb, cap, _ = forest.bucket_x.shape
    n_cap = nb * cap
    if delta is not None:
        n_cap += n_idx * delta.x.shape[1]
    kk = min(k, n_cap)

    sel, route_dists, route_cmps = route_select(forest, q, mode=mode)
    sel = widen_underfilled(sel, index_members(forest, delta), kk)
    bounds = bucket_bounds(forest, q, sel, beam=beam)
    dbounds = None
    if delta is not None:
        dbounds = delta_bounds(delta, q, sel, beam=beam)
    out = scan_sorted(
        forest, q, bounds, kk=kk, beam=beam, delta=delta, dbounds=dbounds,
    )
    stats = scan_stats(route_dists, route_cmps, out, kk=kk)
    rows = VisitRows(
        order=bounds.order,
        visits=out.visits_main[None],
        dorder=None if dbounds is None else dbounds.order,
        dvisits=None if delta is None else (out.visits - out.visits_main)[None],
    )
    return jnp.sqrt(out.top_d), out.top_i, stats, rows


@functools.partial(jax.jit, static_argnames=("k",))
def knn_exact(x: Array, q: Array, *, k: int) -> tuple[Array, Array]:
    """Brute-force oracle: exact kNN of q (Q, D) in x (N, D)."""
    d2 = pairwise(q, x, metric="sq_l2")
    neg, idx = jax.lax.top_k(-d2, min(k, x.shape[0]))
    return jnp.sqrt(jnp.maximum(-neg, 0.0)), idx
