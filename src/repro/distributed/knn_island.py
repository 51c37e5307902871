"""The sharded forest island: ONE ``shard_map`` for search and ingest.

The forest's bucket rows and the per-index delta buffers are sharded over
the ``'model'`` mesh axis (leading dimension, NB and I respectively); the
routing state — index centers, radii, neighbor lists, queries — is
replicated.  Inside the island every shard runs the SAME code the
single-device executor runs (``core.knn.route_select`` + ``local_scan``,
``stream.ingest.append_routed``) over its local rows only; the collectives
are confined to the very end:

  search:  per-shard top-kk carry  -> all_gather + global top-k
           (``core.knn.merge_shard_topk`` — the identical merge
           ``serve/retrieval.knn_logits`` runs for the flat datastore),
           cost counters ``psum``-reduced;
  ingest:  per-shard accept masks  -> ``psum`` (a point is accepted iff its
           OWNING shard accepted it; capacity rejects therefore aggregate
           across shards).

Exactness contract (tests/test_sharded_exec.py): the island returns
bitwise-identical (distance, id) results to the single-device executor on
the same data — per-member distance arithmetic is shard-local and
identical, and k-per-shard candidates make the merged global top-k exact.

Padding convention (repro.api.executor pads before placement):
  * bucket rows NB -> ceil(NB/S)*S; pad buckets carry ``bucket_index = I``
    (one past the real index count) and the island extends the selection
    table with one always-False sentinel column, so pad buckets are never
    eligible and the instrumented eligible/bound counts match the single
    path exactly (pad members are additionally id=-1/mask=False).
  * delta rows I -> ceil(I/S)*S; pad rows keep count=0 (never eligible,
    never routed to — routing only emits real index ids).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import knn as cknn
from repro.distributed import context as dctx
from repro.stream.ingest import DeltaBuffer, append_routed

Array = jax.Array


@functools.lru_cache(maxsize=None)
def default_mesh(shards: int, axis: str = dctx.MODEL_AXIS) -> Mesh:
    """One-axis mesh over the first ``shards`` local devices.

    Cached so every consumer (executor backend, serving datastore path)
    that asks for the same (shards, axis) gets the SAME mesh object —
    placements line up and jit caches key consistently.
    """
    return Mesh(np.asarray(jax.devices()[:shards]), (axis,))


def forest_specs(forest: cknn.DeviceForest, axis: str) -> cknn.DeviceForest:
    """Partition specs for a DeviceForest: bucket rows sharded, routing
    state replicated (the spec tree ``shard_map``/``NamedSharding`` take)."""
    row = P(axis)
    return cknn.DeviceForest(
        index_centers=P(),
        index_radii=P(),
        neighbors=P(),
        bucket_x=row,
        bucket_ids=row,
        bucket_mask=row,
        bucket_pivot=row,
        bucket_radius=row,
        bucket_index=row,
        bucket_scale=None if forest.bucket_scale is None else row,
    )


def delta_view_specs(axis: str) -> cknn.DeltaView:
    row = P(axis)
    return cknn.DeltaView(x=row, ids=row, mask=row, pivot=row, radius=row)


def delta_buffer_specs(axis: str) -> DeltaBuffer:
    row = P(axis)
    return DeltaBuffer(
        x=row, ids=row, count=row, pivot=row, radius=row, sum_x=row,
        main_count=row, main_sum=row, main_radius=row, dropped=row,
    )


def sharded_search(
    mesh: Mesh,
    axis: str,
    forest: cknn.DeviceForest,
    q: Array,
    delta: cknn.DeltaView | None,
    *,
    k: int,
    mode: str = "forest",
    beam: int = 1,
    kernel: bool = True,
    per_island: bool = False,
    explain: bool = False,
    host_sel: Array | None = None,
) -> tuple[Array, ...]:
    """Sharded twin of ``core.knn.knn_search_impl`` — same signature shape,
    same return triple, bitwise-identical results.  ``per_island=True``
    appends a fourth element, ``core.knn.IslandStats`` with one row per
    shard, exposing which island paid which node accesses (the telemetry
    layer's load-balance view; the summed ``SearchStats`` is unchanged).
    ``explain=True`` (implies ``per_island``) appends a fifth,
    ``core.knn.VisitRows``: the col-stacked per-shard sorted visit orders
    (shard-LOCAL row ids — the bounds island's order tables verbatim) plus
    (S, Q) main/delta visited counts, the attribution layer's evidence.

    TWO ``shard_map`` regions, not one: the bounds island (routing +
    eligibility + pivot lower bounds + the SORTED visit order) and the scan
    island (the bounded ``while_loop`` scan + cross-shard merge).  They must
    be separate because XLA's SPMD partitioner miscompiles a sort whose
    result feeds a ``while_loop`` inside the same manually-sharded region
    under an outer ``jit`` (shards silently read other queries' visit
    orders; seen on the CPU backend).  Crossing an island boundary turns
    the sorted order into an ordinary sharded operand, which partitions
    correctly —
    and the split costs nothing: both islands fuse into the same jitted
    executable, and the eager path runs the same ops ``local_scan`` runs.

    Each shard routes the (replicated) queries, scans its local bucket rows
    and local delta rows with the shared ``scan_sorted`` body, then the
    k-per-shard carries merge via ``merge_shard_topk``.  Per-query cost
    counters leave the island as stacked per-shard rows and are summed
    outside it (an int32 sum — the same fleet totals the old in-island
    ``psum`` produced) so the per-island breakdown stays available;
    ``steps`` sums per-shard trip counts (each shard's bounded scan
    terminates on its local bound ordering, so the total can legally
    exceed the single-device count even though the RESULTS are identical).

    ``host_sel`` ((Q, S) bool, replicated math upstream) is the routing
    tier's per-query host-eligibility table (distributed/router/): a False
    (query, shard) pair masks that shard's bucket/delta selection for the
    query AND kills its scan loop (``scan_sorted``'s ``qmask``), so a
    pruned host does ZERO bound evaluations and ZERO member scans for the
    query and its carry stays (+inf, -1) — which contributes nothing to
    ``merge_shard_topk``.  Soundness (hosts are only pruned when their
    metric lower bound exceeds a valid upper bound on the merged kth-best)
    is the router's contract; under it results stay bitwise-identical to
    ``host_sel=None`` (tests/test_routed_exec.py gates this).
    """
    S = mesh.shape[axis]
    qn = q.shape[0]
    nb_pad, cap, _ = forest.bucket_x.shape  # global padded row count
    n_cap = nb_pad * cap  # >= real capacity (pad rows are empty)
    if delta is not None:
        n_cap += delta.x.shape[0] * delta.x.shape[1]
    kk = min(k, n_cap)
    have_delta = delta is not None

    def bounds_island(forest_l, q_l, delta_l, hs_l):
        n_idx = forest_l.index_centers.shape[0]
        sel, route_d, route_c = cknn.route_select(
            forest_l, q_l, mode=mode, kernel=kernel
        )
        # under-filled selections widen on the GLOBAL member count, exactly
        # as the single-device executor decides (core.knn.widen_underfilled)
        members = cknn.index_members(forest_l)
        if delta_l is not None:
            i_l = delta_l.x.shape[0]
            owned = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((S * i_l,), jnp.int32),
                jnp.sum(delta_l.mask, axis=1, dtype=jnp.int32),
                jax.lax.axis_index(axis) * i_l, axis=0,
            )
            members = members + owned[:n_idx]
        sel = cknn.widen_underfilled(sel, jax.lax.psum(members, axis), kk)
        if hs_l is not None:
            # routing tier: this shard bounds/scans only the queries that
            # elected it — (Q, 1) local column broadcast over the I indexes.
            # Routing counters above stay untouched (every host still routes
            # the replicated queries; the saving is in bounds + scans).
            sel = sel & hs_l
        # sentinel column: pad buckets own index I -> always ineligible
        bucket_sel = jnp.pad(sel, ((0, 0), (0, 1)))
        mb = cknn.bucket_bounds(
            forest_l, q_l, bucket_sel, beam=beam, kernel=kernel
        )
        # replicated values leave as an explicit (1, Q) shard slice — the
        # caller reads shard 0 — rather than a P() output, so correctness
        # never leans on the partitioner's replication bookkeeping
        outs = (route_d[None], route_c[None], mb.order, mb.lb_sorted,
                mb.n_elig[None])
        if delta_l is not None:
            i_l = delta_l.x.shape[0]
            # local slice of the global per-index selection table (padded to
            # the sharded row count; pad rows select False)
            sel_pad = jnp.pad(sel, ((0, 0), (0, S * i_l - n_idx)))
            off = jax.lax.axis_index(axis) * i_l
            dsel = jax.lax.dynamic_slice_in_dim(sel_pad, off, i_l, axis=1)
            db = cknn.delta_bounds(delta_l, q_l, dsel, beam=beam, kernel=kernel)
            outs += (db.order, db.lb_sorted, db.n_elig[None])
        return outs

    def scan_island(forest_l, q_l, delta_l, order_l, lbs_l, dorder_l, dlbs_l,
                    hs_l):
        mb = cknn.PhaseBounds(
            order=order_l, lb_sorted=lbs_l,
            n_elig=jnp.zeros((qn,), jnp.int32),  # summed outside the island
        )
        db = None
        if delta_l is not None:
            db = cknn.PhaseBounds(
                order=dorder_l, lb_sorted=dlbs_l,
                n_elig=jnp.zeros((qn,), jnp.int32),
            )
        out = cknn.scan_sorted(
            forest_l, q_l, mb, kk=kk, beam=beam, kernel=kernel,
            delta=delta_l, dbounds=db,
            qmask=None if hs_l is None else hs_l[:, 0],
        )
        top_d, top_i = cknn.merge_shard_topk(
            out.top_d, out.top_i, k=kk, axis_name=axis
        )
        # counters leave as explicit (1, Q) shard rows (stacked to (S, Q)
        # by the out_spec) instead of psum-replicated totals: the caller
        # sums them for SearchStats AND keeps the per-island breakdown
        outs = (top_d, top_i, out.visits[None], out.ndist[None],
                out.npad[None], out.steps[None], out.inserts[None])
        if explain:
            outs += (out.visits_main[None],)
        return outs

    fspec = forest_specs(forest, axis)
    dspec = None if delta is None else delta_view_specs(axis)
    col = P(None, axis)  # (Q, NB) tables sharded along the bucket axis
    row = P(axis, None)  # per-shard (1, Q) vectors stacked to (S, Q)
    hspec = None if host_sel is None else col  # (Q, S) -> (Q, 1) per shard
    bounds_out = (row, row, col, col, row)
    if have_delta:
        bounds_out += (col, col, row)
    bounds_fn = jax.shard_map(
        bounds_island,
        mesh=mesh,
        in_specs=(fspec, P(), dspec, hspec),
        out_specs=bounds_out,
        check_vma=False,
    )
    scan_out = (P(), P(), row, row, row, P(axis), row)
    if explain:
        per_island = True
        scan_out += (row,)
    scan_fn = jax.shard_map(
        scan_island,
        mesh=mesh,
        in_specs=(fspec, P(), dspec, col, col,
                  col if have_delta else None, col if have_delta else None,
                  hspec),
        out_specs=scan_out,
        check_vma=False,
    )

    bout = bounds_fn(forest, q, delta, host_sel)
    route_d, route_c, order, lbs, n_elig = bout[:5]
    dorder = dlbs = None
    n_elig_d_s = jnp.zeros((S, qn), jnp.int32)
    if have_delta:
        dorder, dlbs, n_elig_d_s = bout[5:]
    sout = scan_fn(forest, q, delta, order, lbs, dorder, dlbs, host_sel)
    top_d, top_i, visits_s, ndist_s, npad_s, steps_s, inserts_s = sout[:7]
    merged = cknn.ScanOut(
        top_d=top_d,
        top_i=top_i,
        visits=jnp.sum(visits_s, axis=0, dtype=jnp.int32),
        ndist=jnp.sum(ndist_s, axis=0, dtype=jnp.int32),
        npad=jnp.sum(npad_s, axis=0, dtype=jnp.int32),
        steps=jnp.sum(steps_s, dtype=jnp.int32),
        n_elig=jnp.sum(n_elig, axis=0, dtype=jnp.int32),
        n_elig_d=jnp.sum(n_elig_d_s, axis=0, dtype=jnp.int32),
        inserts=jnp.sum(inserts_s, axis=0, dtype=jnp.int32),
    )
    stats = cknn.scan_stats(route_d[0], route_c[0], merged, kk=kk)
    if not per_island:
        return jnp.sqrt(top_d), top_i, stats
    # per-shard bound work: every shard routes the replicated queries itself
    # (route_d rows) and bounds its own eligible bucket/delta rows
    island = cknn.IslandStats(
        buckets_visited=visits_s,
        distances=ndist_s,
        bound_distances=route_d + n_elig + n_elig_d_s,
    )
    if not explain:
        return jnp.sqrt(top_d), top_i, stats, island
    visits_main_s = sout[7]
    rows = cknn.VisitRows(
        order=order,
        visits=visits_main_s,
        dorder=dorder,
        dvisits=None if not have_delta else visits_s - visits_main_s,
    )
    return jnp.sqrt(top_d), top_i, stats, island, rows


def sharded_ingest(
    mesh: Mesh,
    axis: str,
    centers: Array,
    delta: DeltaBuffer,
    xb: Array,
    ids: Array,
    valid: Array,
) -> tuple[DeltaBuffer, Array]:
    """Sharded twin of ``stream.ingest.ingest_impl``: collective scatter.

    The batch is replicated; every shard routes it against the (replicated)
    index centers, claims the rows whose destination buffer it owns, and
    appends them with the shared ``append_routed`` body — rows owned by
    other shards arrive parked, so they consume no slots and count nowhere
    on this shard.  The per-shard accept masks are disjoint by construction
    (one owner per destination row), so a ``psum`` aggregates capacity
    accepts/rejects across shards exactly.
    """

    def island(centers_r, delta_l, xb_r, ids_r, valid_r):
        xb_f = xb_r.astype(jnp.float32)
        ids_i = ids_r.astype(jnp.int32)
        _, idx = cknn.route_points(centers_r, xb_f, kernel=True)  # (B,) global
        i_l = delta_l.count.shape[0]
        off = jax.lax.axis_index(axis) * i_l
        local = idx - off
        mine = valid_r & (local >= 0) & (local < i_l)
        new_delta, acc = append_routed(
            delta_l, xb_f, ids_i, jnp.where(mine, local, i_l), mine
        )
        acc_any = jax.lax.psum(acc.astype(jnp.int32), axis_name=axis) > 0
        return new_delta, acc_any

    dspec = delta_buffer_specs(axis)
    fn = jax.shard_map(
        island,
        mesh=mesh,
        in_specs=(P(), dspec, P(), P(), P()),
        out_specs=(dspec, P()),
        check_vma=False,
    )
    return fn(centers, delta, xb, ids, valid)
