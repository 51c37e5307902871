"""Dispatch pricing: targeted (heterogeneous) vs fan-all (homogeneous).

DIMS's split, priced with the repo's own currencies and FLEET semantics —
a pruned host receives nothing, so it skips its whole per-query pipeline,
not just the merge:

  wire     the ring all-gather rule the HLO analyzer applies to measured
           collectives (``estimator.estimate_allgather_bytes``): the kNN
           merge gathers each participating host's (distance, id) top-k.
  route    every participating host routes the query against all I index
           centers (one D-dim read per center).
  bounds   each participating host bounds its non-empty buckets of the
           query's selected indexes (one D-dim pivot read per bound —
           the paper's ``bound_distances`` counter, in bytes).
  scan     expected member distances: the selected members the host owns
           (a host scans selected buckets only; a query whose selection
           holds fewer than kk members selects everything, and then the
           pruning rule prunes nothing).
  router   targeted dispatch additionally pays the routing tier itself
           (distance rows to S host centers and I delta pivots), which the
           homogeneous path never computes — so when pruning saves
           nothing, fan-all wins and the program degenerates to the plain
           sharded search.

All terms are traced scalars: the ``fanout='auto'`` decision happens INSIDE
the compiled search program, per query batch.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.estimator import estimate_allgather_bytes
from repro.distributed.router.table import RoutingTable

Array = jax.Array

# one merged candidate on the wire: (f32 distance, i32 id)
_PAIR_BYTES = 8.0


class DispatchCost(NamedTuple):
    """Traced pricing of one query batch (all scalars, f32 bytes)."""

    cost_targeted: Array  # wire + per-host work + routing-tier overhead
    cost_fanall: Array  # wire + per-host work at full fan-out
    wire_targeted: Array  # est. cross-host all-gather bytes, eligible subset
    wire_fanall: Array  # est. cross-host all-gather bytes, whole fleet


def price_dispatch(
    table: RoutingTable, elig: Array, sel: Array, kk: int, *, n_dim: int
) -> DispatchCost:
    """Price both dispatch modes for a batch with eligibility ``elig``
    (Q, S) and scan selection ``sel`` (Q, I)."""
    qn, s_hosts = elig.shape
    n_idx = table.count_hi.shape[1]
    payload = kk * _PAIR_BYTES
    wire_t = jnp.sum(
        estimate_allgather_bytes(payload, jnp.sum(elig, axis=1))
    )
    wire_a = qn * estimate_allgather_bytes(payload, s_hosts)

    vec_bytes = 4.0 * n_dim  # one D-dim f32 row read
    sel_f = sel.astype(jnp.float32)
    # per-(query, host) work if the host participates
    b_qh = sel_f @ table.nbuckets_hi.T.astype(jnp.float32)  # bound evals
    m_qh = sel_f @ table.count_hi.T.astype(jnp.float32)  # selected members
    work_qh = (n_idx + b_qh + m_qh) * vec_bytes
    work_t = jnp.sum(jnp.where(elig, work_qh, 0.0))
    work_a = jnp.sum(work_qh)

    # routing-tier overhead the homogeneous path skips: per query, distance
    # rows to S host centers and I delta pivots (index-center distances are
    # paid by the route step either way and cancel)
    overhead = qn * (s_hosts + n_idx) * vec_bytes
    return DispatchCost(
        cost_targeted=wire_t + work_t + overhead,
        cost_fanall=wire_a + work_a,
        wire_targeted=wire_t,
        wire_fanall=wire_a,
    )
