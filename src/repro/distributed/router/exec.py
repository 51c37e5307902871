"""``routed_search`` — the routing tier composed over the shard islands.

Same contract as ``knn_island.sharded_search`` with one extra trailing
element, :class:`RouterStats`.  The routing math (eligibility + pricing)
runs REPLICATED outside the islands — at fleet scale every host holds the
same table and derives the same eligibility independently; here that is one
untraced prefix of the same jitted program — and the decision flows into
the islands as ``sharded_search``'s ``host_sel`` operand.

Fanout semantics (RoutingConfig.fanout):
  'all'       homogeneous: ``host_sel=None`` — literally the plain sharded
              program (the router only reports its would-be eligibility).
  'targeted'  heterogeneous: always mask to the eligible set.
  'auto'      DIMS's cost-model choice, decided per query batch INSIDE the
              compiled program (a traced bool): targeted iff its priced
              cost undercuts fan-all.  The fan-all branch resolves to an
              all-True mask, which is arithmetically identity — results are
              bitwise identical to 'all' either way.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import knn as cknn
from repro.core.metric import pairwise
from repro.distributed import knn_island
from repro.distributed.router.cost import price_dispatch
from repro.distributed.router.table import RoutingTable, host_eligibility

Array = jax.Array


class RouterStats(NamedTuple):
    """Per-batch routing telemetry (device; fetched with SearchStats)."""

    eligible_hosts: Array  # (Q,) i32 hosts the lower bounds could not prune
    pruned_hosts: Array  # (Q,) i32 hosts actually skipped post-decision
    targeted: Array  # () bool: heterogeneous dispatch chosen
    wire_targeted: Array  # () f32 est. cross-host bytes, eligible subset
    wire_fanall: Array  # () f32 est. cross-host bytes, whole fleet
    cost_targeted: Array  # () f32 full targeted price (wire+bounds+overhead)
    cost_fanall: Array  # () f32 full fan-all price


def routed_search(
    mesh,
    axis: str,
    forest: cknn.DeviceForest,
    q: Array,
    delta: cknn.DeltaView | None,
    table: RoutingTable,
    *,
    k: int,
    mode: str = "forest",
    beam: int = 1,
    kernel: bool = True,
    fanout: str = "auto",
    per_island: bool = False,
    explain: bool = False,
) -> tuple[Array, ...]:
    """Routing tier + sharded islands; appends RouterStats to the island
    tuple.  Exactness: bitwise-identical (distances, ids) to
    ``sharded_search`` fan-all and to the single-device executor — the
    eligibility rule only prunes hosts whose metric lower bound strictly
    clears a valid upper bound on the merged kth-best (table.py)."""
    s_hosts = mesh.shape[axis]
    qn, n_dim = q.shape
    n_idx = forest.index_centers.shape[0]
    nb_pad, cap, _ = forest.bucket_x.shape
    n_cap = nb_pad * cap
    if delta is not None:
        n_cap += delta.x.shape[0] * delta.x.shape[1]
    kk = min(k, n_cap)

    def routing_island(forest_l, q_l):
        d_sq_l, _ = cknn.route_points(forest_l.index_centers, q_l, kernel=kernel)
        sel_l, _, _ = cknn.route_select(forest_l, q_l, mode=mode, kernel=kernel)
        return d_sq_l[None], sel_l[None]

    # the routing kernels run per shard, as in the islands (the compiler
    # cannot partition a Pallas call); every shard computes the same
    # replicated rows and shard 0's copy is read — the very selection the
    # bounds island derives
    d_sq, sel = jax.shard_map(
        routing_island,
        mesh=mesh,
        in_specs=(knn_island.forest_specs(forest, axis), P()),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    )(forest, q)
    d_center = jnp.sqrt(d_sq[0])
    sel = sel[0]
    d_host = pairwise(q, table.host_centers, metric="l2", use_kernel=False)
    dkw = {}
    if delta is not None:
        # live buffer state for the LOGICAL rows (operand-padded to a shard
        # multiple; pad rows never carry members)
        dkw = dict(
            d_delta=pairwise(
                q, delta.pivot[:n_idx], metric="l2", use_kernel=False
            ),
            delta_radius=delta.radius[:n_idx],
            delta_count=jnp.sum(
                delta.mask[:n_idx], axis=1, dtype=jnp.int32
            ),
        )
    elig, _ = host_eligibility(table, d_center, d_host, sel, kk, **dkw)
    cost = price_dispatch(table, elig, sel, kk, n_dim=n_dim)

    if fanout == "all":
        host_sel = None
        targeted = jnp.asarray(False)
    elif fanout == "targeted":
        host_sel = elig
        targeted = jnp.asarray(True)
    elif fanout == "auto":
        targeted = cost.cost_targeted < cost.cost_fanall
        host_sel = elig | ~targeted
    else:
        raise ValueError(f"fanout {fanout!r}")

    outs = knn_island.sharded_search(
        mesh, axis, forest, q, delta,
        k=k, mode=mode, beam=beam, kernel=kernel,
        per_island=per_island, explain=explain, host_sel=host_sel,
    )
    pruned = (
        jnp.zeros((qn,), jnp.int32) if host_sel is None
        else jnp.sum(~host_sel, axis=1, dtype=jnp.int32)
    )
    router = RouterStats(
        eligible_hosts=jnp.sum(elig, axis=1, dtype=jnp.int32),
        pruned_hosts=pruned,
        targeted=targeted,
        wire_targeted=cost.wire_targeted,
        wire_fanall=cost.wire_fanall,
        cost_targeted=cost.cost_targeted,
        cost_fanall=cost.cost_fanall,
    )
    return (*outs, router)
