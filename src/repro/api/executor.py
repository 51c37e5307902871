"""Device-layout executor backends — the seam every layer shares.

``OverlapIndex`` does not talk to devices directly anymore: a *backend*
resolved from ``cfg.layout`` (``make_backend``) owns

  * forest upload    — ``upload_forest``: DeviceForest placement (quantized
                       per config; the sharded backend pads bucket rows to
                       a shard multiple and places them NB-sharded),
  * delta placement  — ``place_delta`` / ``logical_delta``: the facade's
                       monitor, persistence and introspection always see
                       the LOGICAL unpadded buffers, search/ingest the
                       device-resident (possibly padded + sharded) ones,
  * executor bodies  — ``search_body`` / ``ingest_body``: the un-jitted
                       callables the plan layer (api/plan.py) and the
                       facade wrap with trace counters + ``jax.jit``.  The
                       single backend wraps ``core.knn.knn_search_impl`` /
                       ``stream.ingest.ingest_impl``; the sharded backend
                       returns the ``distributed/knn_island.py`` islands.
                       One search body per layout serves both plan kinds:
                       it returns ``(dists, ids, SearchStats, IslandStats)``
                       — the fourth element is the telemetry layer's
                       per-island node-access breakdown (one row per shard;
                       a singleton row on the single layout) — plus
                       ``VisitRows`` when ``key.explain`` is set,
  * swap barrier     — ``barrier``: the sharded layout blocks until every
                       shard's new arrays are materialized before a
                       maintenance rebuild swaps them in, keeping
                       ``swap_trees`` hot-swaps atomic under sharding.

Quantization order matters for exactness: the sharded upload quantizes the
UNPADDED members first (identical per-member int8 scales to the single
path) and only then pads — int8 searches stay bitwise-identical across
layouts.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.config import ConfigError, LayoutConfig
from repro.core.forest import ForestArrays
from repro.core.knn import (
    DeviceForest,
    IslandStats,
    device_forest,
    knn_search_impl,
)
from repro.kernels import ops as kops
from repro.stream.ingest import DeltaBuffer, ingest_impl


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


class SingleDeviceBackend:
    """The default layout: whole forest + delta on one device.  Bodies are
    the core executors verbatim — zero overhead over the pre-layout code."""

    kind = "single"
    shards = 1

    def upload_forest(self, forest: ForestArrays, *, quantize: bool) -> DeviceForest:
        return device_forest(forest, quantize=quantize)

    def place_delta(self, delta: DeltaBuffer) -> DeltaBuffer:
        return delta

    def logical_delta(self, delta: DeltaBuffer, n_indexes: int) -> DeltaBuffer:
        return delta

    def search_body(self, key):
        def body(forest, q, delta):
            d, i, s, rows = knn_search_impl(
                forest, q, k=key.k, mode=key.mode, beam=key.beam, delta=delta,
            )
            # one island: the per-island telemetry view is the fleet view
            # with a leading singleton dim (free — no extra device work)
            isl = IslandStats(
                buckets_visited=s.buckets_visited[None],
                distances=s.distances[None],
                bound_distances=s.bound_distances[None],
            )
            # a search plan drops the visit rows: dead code under jax.jit
            return (d, i, s, isl, rows) if key.explain else (d, i, s, isl)

        return body

    def ingest_body(self):
        return ingest_impl

    def search_operands(self, device_forest):
        """First operand the plan executor is called with.  The routed
        backend overrides this to bundle the routing table alongside the
        forest — as a traced OPERAND, so a rebuild-swapped table reaches
        already-compiled plans without retracing (a closure capture would
        bake the stale table into the executable)."""
        return device_forest

    def barrier(self, *trees) -> None:
        # single device: the facade's swap assignment is already atomic
        return None


class ShardedBackend:
    """Bucket rows + delta buffers sharded over ``shards`` devices along one
    mesh axis; executor bodies are the shard_map islands."""

    kind = "sharded"

    def __init__(self, shards: int, axis: str = "model") -> None:
        from repro.distributed import knn_island

        self.shards = int(shards)
        self.axis = axis
        self._island = knn_island
        self.mesh = knn_island.default_mesh(self.shards, axis)

    # -- placement -----------------------------------------------------------
    def _put(self, x, *, sharded: bool):
        spec = P(self.axis) if sharded else P()
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def upload_forest(self, forest: ForestArrays, *, quantize: bool) -> DeviceForest:
        nb, cap, dim = forest.bucket_x.shape
        n_idx = forest.n_indexes
        nb_pad = _ceil_to(nb, self.shards)

        # quantize BEFORE padding: per-member scales identical to the single
        # path's device_forest, so int8 results stay bitwise-identical
        bucket_x = jnp.asarray(forest.bucket_x)
        bucket_scale = None
        if quantize:
            xq, scale = kops.quantize_datastore(bucket_x.reshape(nb * cap, dim))
            bucket_x = xq.reshape(nb, cap, dim)
            bucket_scale = scale.reshape(nb, cap)

        pad = nb_pad - nb
        bucket_ids = np.asarray(forest.bucket_ids)
        bucket_mask = np.asarray(forest.bucket_mask)
        bucket_pivot = np.asarray(forest.bucket_pivot)
        bucket_radius = np.asarray(forest.bucket_radius)
        # pad buckets are owned by sentinel index I: the island extends the
        # selection table with an always-False column there, so they are
        # never eligible and never counted
        bucket_index = np.concatenate(
            [np.asarray(forest.bucket_index),
             np.full((pad,), n_idx, np.int32)]
        )
        if pad:
            bucket_x = jnp.concatenate(
                [bucket_x, jnp.zeros((pad, cap, dim), bucket_x.dtype)]
            )
            bucket_ids = np.concatenate(
                [bucket_ids, np.full((pad, cap), -1, bucket_ids.dtype)]
            )
            bucket_mask = np.concatenate(
                [bucket_mask, np.zeros((pad, cap), bool)]
            )
            bucket_pivot = np.concatenate(
                [bucket_pivot, np.zeros((pad, dim), bucket_pivot.dtype)]
            )
            bucket_radius = np.concatenate(
                [bucket_radius, np.zeros((pad,), bucket_radius.dtype)]
            )
            if bucket_scale is not None:
                bucket_scale = jnp.concatenate(
                    [bucket_scale, jnp.ones((pad, cap), bucket_scale.dtype)]
                )
        return DeviceForest(
            index_centers=self._put(
                np.asarray(forest.index_centers), sharded=False
            ),
            index_radii=self._put(np.asarray(forest.index_radii), sharded=False),
            neighbors=self._put(np.asarray(forest.neighbors), sharded=False),
            bucket_x=self._put(bucket_x, sharded=True),
            bucket_ids=self._put(bucket_ids, sharded=True),
            bucket_mask=self._put(bucket_mask, sharded=True),
            bucket_pivot=self._put(bucket_pivot, sharded=True),
            bucket_radius=self._put(bucket_radius, sharded=True),
            bucket_index=self._put(bucket_index, sharded=True),
            bucket_scale=(
                None if bucket_scale is None
                else self._put(bucket_scale, sharded=True)
            ),
        )

    def place_delta(self, delta: DeltaBuffer) -> DeltaBuffer:
        n_idx = delta.count.shape[0]
        pad = _ceil_to(n_idx, self.shards) - n_idx

        def leaf(x):
            x = jnp.asarray(x)
            if pad:
                # pad rows stay empty forever: count=0 makes them ineligible
                # for search and routing only emits real index ids
                x = jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]
                )
            return self._put(x, sharded=True)

        return DeltaBuffer(*[leaf(x) for x in delta])

    def logical_delta(self, delta: DeltaBuffer, n_indexes: int) -> DeltaBuffer:
        return DeltaBuffer(*[x[:n_indexes] for x in delta])

    # -- executor bodies -----------------------------------------------------
    def search_body(self, key):
        def body(forest, q, delta):
            return self._island.sharded_search(
                self.mesh, self.axis, forest, q, delta,
                k=key.k, mode=key.mode, beam=key.beam,
                per_island=True, explain=key.explain,
            )

        return body

    def ingest_body(self):
        def body(centers, delta, xb, ids, valid):
            return self._island.sharded_ingest(
                self.mesh, self.axis, centers, delta, xb, ids, valid
            )

        return body

    def search_operands(self, device_forest):
        return device_forest

    def barrier(self, *trees) -> None:
        """Block until every shard of the given trees is materialized —
        called right before a maintenance rebuild's hot swap, so a
        concurrent query can never observe a half-placed forest/delta."""
        jax.block_until_ready(trees)


class RoutedBackend(ShardedBackend):
    """The sharded layout plus the routing tier (distributed/router/):
    a replicated :class:`~repro.distributed.router.RoutingTable` rebuilt at
    every forest upload (build, load — including the host-count clamp —
    and maintenance rebuild swaps all funnel through ``upload_forest``),
    and executor bodies that run ``routed_search`` instead of the bare
    island.  Search bodies append ``RouterStats`` to the island tuple."""

    kind = "routed"

    def __init__(self, shards: int, axis: str = "model", *, routing=None):
        from repro.api.config import RoutingConfig
        from repro.distributed import router

        super().__init__(shards, axis)
        self.routing = routing if routing is not None else RoutingConfig()
        self._router = router
        self.table = None  # replicated device RoutingTable

    def upload_forest(self, forest: ForestArrays, *, quantize: bool) -> DeviceForest:
        dev = super().upload_forest(forest, quantize=quantize)
        self.refresh_table(forest, quantize=quantize)
        return dev

    def refresh_table(self, forest: ForestArrays, *, quantize: bool = False) -> None:
        """(Re)build the routing table from the LOGICAL forest and replicate
        it across the mesh.  Must run on every swap that can move bucket
        ownership — a stale table must never silently mis-route.  An int8
        layout (``quantize``) gets covers around the dequantized members,
        matching the distances its scans actually compute."""
        tab = self._router.build_routing_table(
            forest, self.shards, method=self.routing.overlap_method,
            quantize=quantize,
        )
        self.table = jax.device_put(tab, NamedSharding(self.mesh, P()))

    def search_operands(self, device_forest):
        return (device_forest, self.table)

    def search_body(self, key):
        fanout = key.fanout or self.routing.fanout

        def body(operands, q, delta):
            forest, table = operands
            return self._router.routed_search(
                self.mesh, self.axis, forest, q, delta, table,
                k=key.k, mode=key.mode, beam=key.beam,
                fanout=fanout, per_island=True, explain=key.explain,
            )

        return body


def make_backend(layout: LayoutConfig, *, clamp: bool = False):
    """Resolve a ``cfg.layout`` into a backend.

    ``clamp=True`` (the ``load`` path) downgrades an unsatisfiable shard
    count to what the host has — with a warning — instead of failing: a
    snapshot saved on an 8-device host must still load on a laptop.
    Explicit builds stay strict and raise with the XLA override hint.
    """
    if layout.kind == "single":
        return SingleDeviceBackend()
    avail = jax.device_count()
    shards = layout.shards or avail
    if shards > avail:
        if not clamp:
            raise ConfigError(
                f"LayoutConfig.shards={shards} exceeds the {avail} visible "
                "device(s); on CPU force a host mesh with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N (set "
                "before jax initializes) or lower shards"
            )
        warnings.warn(
            f"snapshot asked for {shards} shards but only {avail} device(s) "
            f"are visible; re-sharding to {avail}",
            stacklevel=2,
        )
        shards = avail
    if shards == 1:
        # one effective host: routing degenerates (every query has exactly
        # one eligible host), so both kinds collapse to the single layout
        return SingleDeviceBackend()
    if layout.kind == "routed":
        return RoutedBackend(shards, layout.axis, routing=layout.routing)
    return ShardedBackend(shards, layout.axis)
