"""kNN-LM retrieval at the LM head — the paper's technique as a first-class
serving feature.

The datastore holds (key, next-token) pairs organized by the paper's
overlap-optimized forest (core/).  At each decode step the hidden state
queries the datastore; the neighbor distribution is interpolated with the
model distribution:

    p(y) = lam * p_knn(y) + (1 - lam) * p_lm(y)
    p_knn(y)  proportional to  sum_{(k_i, v_i) in topK, v_i = y} exp(-d_i / T)

Distributed layout: the datastore is sharded over the 'model' axis inside a
shard_map island — each shard scans its local rows with the fused Pallas
distance+top-k kernel, then a k-per-shard all_gather + global top-k merges
(collective volume: k * (1 + 1) floats per query per shard, NOT the
datastore).  Alg. 2's "run kNN on the selected indexes in parallel" maps
exactly onto this island (DESIGN.md §3).

Datastore variants:
  * flat      — brute-force shard scan (fused kernel), exact;
  * forest    — the paper's overlap-optimized forest, pruned scan (host
                builds the forest; device search via core.knn);
  * quantized — int8 rows (beyond-paper memory-roofline lever, kernels/).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import context as dctx
from repro.kernels import ops as kops

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclass
class Datastore:
    keys: Array  # (N, Dk) f32 or int8 (quantized)
    values: Array  # (N,) i32 token ids
    scale: Array | None = None  # (N,) per-row int8 scales
    proj: Array | None = None  # (D, Dk) optional query down-projection


def build_flat_datastore(
    keys: np.ndarray, values: np.ndarray, *, quantized: bool = False
) -> Datastore:
    k = jnp.asarray(keys, jnp.float32)
    if quantized:
        kq, scale = kops.quantize_datastore(k)
        return Datastore(keys=kq, values=jnp.asarray(values, jnp.int32), scale=scale)
    return Datastore(keys=k, values=jnp.asarray(values, jnp.int32))


@jax.tree_util.register_dataclass
@dataclass
class ForestDatastore:
    """The paper's overlap-optimized forest as a kNN-LM datastore: queries
    run the pruned masked-bucket scan (core/knn.py) instead of the flat
    shard scan — the fraction of rows touched is the paper's whole point
    (benchmarks/bench_retrieval.py measures it).

    ``delta`` (a stream.ingest.DeltaBuffer, present when the datastore was
    built with ``stream_capacity > 0``) holds streamed (key, token) pairs
    appended at serve time (engine IngestRequest); the search scans it as
    the second phase of the same fused bucket scan.  ``n_main`` is the
    frozen build-time row count; streamed rows take global ids from
    ``n_main`` upward, indexing the preallocated tail of ``values``.
    ``next_id`` is the id high-water mark — it lives ON the datastore (not
    in any engine) so every ingest path shares one id space and an id can
    never be issued twice or past the values tail."""

    forest: Any  # core.knn.DeviceForest
    values: Array  # (N_objects + stream capacity,) i32, by global object id
    delta: Any = None  # stream.ingest.DeltaBuffer | None
    n_main: int = 0
    next_id: int = 0
    # device layout (static: search/ingest branch on it at trace time).
    # 1 = single device; >1 = forest bucket rows + delta buffers sharded over
    # that many devices on the 'model' axis, searches run the
    # distributed/knn_island.py islands.
    shards: int = dataclasses.field(default=1, metadata=dict(static=True))
    # routing tier (routed layout): the replicated RoutingTable rides as a
    # TRACED pytree leaf — a rebuild-swapped table reaches compiled decode
    # steps as a fresh operand — while the dispatch policy is static
    router_table: Any = None  # distributed.router.RoutingTable | None
    fanout: str | None = dataclasses.field(
        default=None, metadata=dict(static=True)
    )


def datastore_from_index(
    ix,
    values: np.ndarray,
    *,
    stream_capacity: int = 0,
    quantized: bool | None = None,
) -> ForestDatastore:
    """Wrap a built ``repro.api.OverlapIndex`` as a serving datastore — the
    implementation behind ``OverlapIndex.to_datastore``.

    ``values[i]`` pairs with object id ``i`` (one per ``ix.n_total``
    object, streamed members included).  The index's live delta buffers (if
    any) ride along unchanged, so already-streamed pairs stay retrievable;
    ``stream_capacity > 0`` preallocates a values tail for that many FUTURE
    serve-side inserts (``ingest_keys`` stops issuing ids at the tail end,
    so an accepted key can never index past it) and — when the index has no
    delta yet — per-index buffers sized ``2 * stream_capacity / n_indexes``
    (floor 32): 2x headroom for routing skew without multiplying memory by
    the index count; a pathologically skewed stream hits the reported
    capacity-reject path instead.

    The index's device layout rides along: forest upload and delta placement
    go through ``ix.backend``, so a sharded index serves a sharded datastore
    (``shards`` recorded on the result) and searches keep running the same
    islands — bitwise-identical to serving the single-device layout."""
    from repro.stream.ingest import alloc_delta

    values = np.asarray(values)
    if len(values) != ix.n_total:
        raise ValueError(
            f"need one value per indexed object: got {len(values)} values "
            f"for {ix.n_total} objects"
        )
    device = (
        ix.device if quantized is None
        else ix.backend.upload_forest(ix.forest, quantize=quantized)
    )
    delta = ix.device_delta  # placed (padded + sharded under that layout)
    vals = jnp.asarray(values, jnp.int32)
    if stream_capacity > 0:
        if delta is None:
            capd = min(
                stream_capacity, -(-2 * stream_capacity // ix.forest.n_indexes)
            )
            delta = ix.backend.place_delta(
                alloc_delta(ix.forest, max(32, capd))
            )
        vals = jnp.concatenate([vals, jnp.zeros((stream_capacity,), jnp.int32)])
    return ForestDatastore(
        forest=device,
        values=vals,
        delta=delta,
        n_main=ix.n_total,
        next_id=ix.n_total,
        shards=ix.backend.shards,
        # routed layout: the backend's table is live after the device upload
        # above; non-routed backends have no table attribute
        router_table=getattr(ix.backend, "table", None),
        fanout=(
            ix.cfg.layout.routing.fanout
            if ix.backend.kind == "routed" else None
        ),
    )


def build_forest_datastore(
    keys: np.ndarray,
    values: np.ndarray,
    *,
    method: str = "vbm",
    eps: float | None = None,
    min_pts: int = 16,
    quantized: bool = False,
    stream_capacity: int = 0,
) -> ForestDatastore:
    """Build the paper's index over the datastore keys and wrap it for
    serving — ``OverlapIndex.build(keys, ...).to_datastore(values, ...)``
    with an eps default derived from the keys (k-dist style heuristic)."""
    from repro.api import Config, IndexConfig, OverlapIndex, SearchConfig

    keys = np.asarray(keys, np.float32)
    if eps is None:
        # k-dist style heuristic: median NN distance of a sample x 2
        g = np.random.default_rng(0)
        sample = keys[g.choice(len(keys), min(2048, len(keys)), replace=False)]
        d2 = ((sample[:, None, :] - sample[None, :, :]) ** 2).sum(-1)
        np.fill_diagonal(d2, np.inf)
        eps = 2.0 * float(np.sqrt(np.median(d2.min(axis=1))))
    ix = OverlapIndex.build(keys, Config(
        index=IndexConfig(method=method, eps=eps, min_pts=min_pts, dbscan_block=2048),
        search=SearchConfig(quantize=quantized),
    ))
    return ix.to_datastore(values, stream_capacity=stream_capacity)


def ingest_keys(
    ds: ForestDatastore, keys: Array, values: Array
) -> tuple[ForestDatastore, int]:
    """Stream (key, token) pairs into a forest datastore's delta buffers.

    Routes + appends via stream.ingest (Alg. 2 STEP-1 routing on device),
    writes token values at the assigned global ids.  Two-phase so the id
    space never leaks: a PROBE ingest (result discarded) learns which pairs
    the buffers will accept, then ids from ``ds.next_id`` are issued to
    exactly those pairs (clamped to the values-tail room) and committed.
    Ids are therefore only ever consumed by pairs that are actually stored
    — a capacity-rejected or tail-refused pair burns nothing and can be
    re-submitted later.  Returns the updated datastore and the number of
    ACCEPTED pairs (the serving tier reports rejects back to the client
    rather than blocking the decode loop on a rebuild; the offline
    StreamingForest wrapper is the no-loss path).
    """
    if ds.delta is None:
        raise ValueError("datastore built without stream_capacity")
    next_id = int(ds.next_id)
    room = ds.values.shape[0] - next_id
    if room <= 0:
        return ds, 0
    keys_j = jnp.asarray(keys, jnp.float32)
    _, acc = _run_ingest(  # probe: same state + same routing => same acceptance
        ds, keys_j, jnp.full((keys_j.shape[0],), -1, jnp.int32)
    )
    # Dropping rejected rows cannot demote an accepted one: within each
    # destination run the kept rows' slot ranks only shrink.
    take = np.flatnonzero(np.asarray(acc))[:room]
    if take.size == 0:
        return ds, 0
    ids = jnp.arange(next_id, next_id + take.size, dtype=jnp.int32)
    new_delta, _ = _run_ingest(ds, keys_j[take], ids)
    new_values = ds.values.at[ids].set(
        jnp.asarray(np.asarray(values)[take], jnp.int32)
    )
    return (
        dataclasses.replace(
            ds, values=new_values, delta=new_delta,
            next_id=next_id + int(take.size),
        ),
        int(take.size),
    )


def _run_ingest(ds: ForestDatastore, keys_j: Array, ids: Array):
    """Route + append one batch under the datastore's device layout: the
    single-device ``stream.ingest`` executor, or the collective-scatter
    island when the buffers are sharded."""
    from repro.stream.ingest import ingest

    if ds.shards > 1:
        from repro.distributed import knn_island

        return knn_island.sharded_ingest(
            knn_island.default_mesh(ds.shards), dctx.MODEL_AXIS,
            ds.forest.index_centers, ds.delta, keys_j, ids,
            jnp.ones((keys_j.shape[0],), jnp.bool_),
        )
    return ingest(ds.forest, ds.delta, keys_j, ids)


def forest_knn(
    hidden: Array, ds: ForestDatastore, k: int, *, kernel: bool = True
) -> tuple[Array, Array]:
    """(distances (B,k), token values (B,k)) via the paper's Alg. 2 search.

    ``kernel`` selects the kernels/ops dispatch path (fused Pallas bucket
    scan on TPU) vs the pure-jnp reference — see core.knn.knn_search_impl.
    Streaming deltas, when present, are scanned as the second phase.
    (Executor, not the legacy jitted entry: this runs INSIDE the engine's
    jitted decode step, which is the compilation boundary.)
    """
    from repro.core.knn import knn_search_impl
    from repro.stream.ingest import delta_view

    delta = None if ds.delta is None else delta_view(ds.delta)
    if ds.shards > 1 and ds.router_table is not None:
        from repro.distributed import router as drouter
        from repro.distributed import knn_island

        d, ids, *_ = drouter.routed_search(
            knn_island.default_mesh(ds.shards), dctx.MODEL_AXIS,
            ds.forest, hidden.astype(jnp.float32), delta, ds.router_table,
            k=k, mode="forest", kernel=kernel,
            fanout=ds.fanout or "auto",
        )
    elif ds.shards > 1:
        from repro.distributed import knn_island

        d, ids, _ = knn_island.sharded_search(
            knn_island.default_mesh(ds.shards), dctx.MODEL_AXIS,
            ds.forest, hidden.astype(jnp.float32), delta,
            k=k, mode="forest", kernel=kernel,
        )
    else:
        d, ids, _ = knn_search_impl(
            ds.forest, hidden.astype(jnp.float32), k=k, mode="forest",
            kernel=kernel, delta=delta,
        )
    vals = ds.values[jnp.clip(ids, 0, ds.values.shape[0] - 1)]
    vals = jnp.where(ids >= 0, vals, 0)
    d = jnp.where(ids >= 0, d, jnp.inf)
    return d * d, vals  # squared distances, matching the flat path


def _local_topk(q: Array, ds: Datastore, k: int) -> tuple[Array, Array]:
    if ds.scale is not None:
        d2 = kops.pairwise_sq_l2_int8(q, ds.keys, ds.scale)
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx
    return kops.knn_topk(q, ds.keys, k=k)


def knn_logits(
    hidden: Array, ds: Datastore, cfg: ModelConfig
) -> Array:
    """p_knn over the padded vocab from datastore neighbors of ``hidden``.

    hidden: (B, D). Runs the sharded scan when a mesh with a 'model' axis is
    active, single-shard otherwise.
    """
    r = cfg.retrieval
    if isinstance(ds, ForestDatastore):
        d2, vals = forest_knn(hidden, ds, r.k, kernel=r.kernel)
        w = jax.nn.softmax(-jnp.sqrt(jnp.maximum(d2, 0.0)) / r.temperature, axis=-1)
        p_knn = jnp.zeros((hidden.shape[0], cfg.padded_vocab), jnp.float32)
        return p_knn.at[jnp.arange(hidden.shape[0])[:, None], vals].add(w)
    q = hidden.astype(jnp.float32)
    if ds.proj is not None:
        q = q @ ds.proj.astype(jnp.float32)

    mesh = dctx.current_mesh()
    tp = dctx.model_axis_size(mesh)
    if mesh is None or tp == 1:
        d2, idx = _local_topk(q, ds, r.k)
        vals = ds.values[idx]  # (B, k)
    else:
        def island(q_l, keys, values, scale):
            from repro.core.knn import merge_shard_topk

            ds_l = Datastore(keys=keys, values=values, scale=scale)
            d2_l, idx_l = _local_topk(q_l, ds_l, r.k)
            # k candidates per shard -> exact global top-k; the identical
            # merge the forest island runs (collective volume is k pairs per
            # query per shard, never the datastore)
            return merge_shard_topk(
                d2_l, values[idx_l], k=r.k, axis_name=dctx.MODEL_AXIS
            )

        scale_spec = P(dctx.MODEL_AXIS) if ds.scale is not None else None
        d2, vals = jax.shard_map(
            island,
            mesh=mesh,
            in_specs=(P(), P(dctx.MODEL_AXIS, None), P(dctx.MODEL_AXIS), scale_spec),
            out_specs=(P(), P()),
            check_vma=False,
        )(q, ds.keys, ds.values, ds.scale)

    w = jax.nn.softmax(-jnp.sqrt(jnp.maximum(d2, 0.0)) / r.temperature, axis=-1)  # (B, k)
    vocab = cfg.padded_vocab
    p_knn = jnp.zeros((hidden.shape[0], vocab), jnp.float32)
    p_knn = p_knn.at[jnp.arange(hidden.shape[0])[:, None], vals].add(w)
    return p_knn


def knn_interpolate(logits: Array, hidden: Array, ds: Datastore, cfg: ModelConfig) -> Array:
    """log of lam * p_knn + (1 - lam) * softmax(logits)."""
    lam = cfg.retrieval.lam
    p_lm = jax.nn.softmax(logits, axis=-1)
    p_knn = knn_logits(hidden, ds, cfg)
    return jnp.log(jnp.maximum((1.0 - lam) * p_lm + lam * p_knn, 1e-20))
