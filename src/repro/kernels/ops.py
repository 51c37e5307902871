"""Public jit'd wrappers for the Pallas kernels — the ONE dispatch layer.

Every distance computed on the serving/search path (index routing, bucket
lower bounds, bucket member scan, flat datastore scan) goes through this
module, so backend tuning happens in exactly one place.

Dispatch policy (each wrapper below):
* On TPU: compiled Pallas kernels with MXU-aligned default tiles.
* ``REPRO_FORCE_PALLAS=1`` in the environment: the Pallas kernel body runs
  under ``interpret=True`` everywhere (slow; Python-interpreted) — this is
  how the kernel test sweeps validate kernel math off-TPU.
* Otherwise (e.g. this container's CPU): the pure-jnp reference from
  ``kernels/ref.py`` — identical math, validated against the kernels in
  tests/test_kernels_pairwise.py and tests/test_bucket_scan.py.

Datastore storage knobs:
* ``quantize_datastore`` produces the symmetric per-row int8 layout; the
  ``*_int8`` kernels and the ``scale=`` argument of ``bucket_scan_topk``
  dequantize in-register (4x less HBM traffic than f32 on the scan).
  The forest equivalent is ``core.knn.device_forest(..., quantize=True)``,
  which stores ``bucket_x`` int8 with per-member scales.

Streaming delta buckets (repro.stream): the per-index append buffers are
scanned by the SAME fused bucket-scan kernel — a delta buffer is just a
bucket datastore of shape (I, CAP_d, D) with -1-id padding, so
``bucket_scan_prepad`` + ``bucket_scan_topk`` (alias ``delta_scan_topk``)
cover the delta phase of ``core.knn.knn_search_impl`` with no new kernel.
Delta members always scan f32 (``scale=None``) even when the main forest
is int8-quantized: freshly streamed rows have no quantization pass yet —
they pick up int8 storage when maintenance absorbs them into the tree.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bucket_scan import bucket_scan_topk_pallas, prepad_buckets
from repro.kernels.pairwise_l2 import (
    eps_count_pallas,
    eps_min_label_pallas,
    eps_nearest_core_pallas,
    pairwise_sq_l2_int8_pallas,
    pairwise_sq_l2_pallas,
)
from repro.kernels.topk import knn_topk_pallas

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _force_pallas() -> bool:
    return os.environ.get("REPRO_FORCE_PALLAS", "0") == "1"


def pairwise_sq_l2(q: Array, x: Array) -> Array:
    """(Q, D) x (N, D) -> (Q, N) squared L2 distances."""
    if _on_tpu():
        return pairwise_sq_l2_pallas(q, x)
    if _force_pallas():
        return pairwise_sq_l2_pallas(q, x, bq=64, bn=64, bd=64, interpret=True)
    return ref.pairwise_sq_l2_ref(q, x)


def pairwise_sq_l2_int8(q: Array, x_q: Array, scale: Array) -> Array:
    """f32 queries vs int8 per-row-quantized datastore."""
    if _on_tpu():
        return pairwise_sq_l2_int8_pallas(q, x_q, scale)
    if _force_pallas():
        return pairwise_sq_l2_int8_pallas(q, x_q, scale, bq=64, bn=64, bd=64, interpret=True)
    return ref.pairwise_sq_l2_int8_ref(q, x_q, scale)


def eps_count(q: Array, x: Array, eps_sq: Array) -> Array:
    """DBSCAN core test: per-query count of eps-neighbors (thresholding
    fused into the distance tiles — no (Q, N) block reaches HBM)."""
    if _on_tpu():
        return eps_count_pallas(q, x, eps_sq)
    if _force_pallas():
        return eps_count_pallas(q, x, eps_sq, bq=64, bn=64, interpret=True)
    return ref.eps_count_ref(q, x, eps_sq)


def eps_min_label(
    q: Array, x: Array, labels: Array, core: Array, eps_sq: Array
) -> Array:
    """DBSCAN label sweep: min label over core eps-neighbors (N if none)."""
    if _on_tpu():
        return eps_min_label_pallas(q, x, labels, core, eps_sq)
    if _force_pallas():
        return eps_min_label_pallas(
            q, x, labels, core, eps_sq, bq=64, bn=64, interpret=True
        )
    return ref.eps_min_label_ref(q, x, labels, core, eps_sq)


def eps_nearest_core(
    q: Array, x: Array, labels: Array, core: Array
) -> tuple[Array, Array]:
    """DBSCAN border pass: (d2, label) of each query's nearest core point."""
    if _on_tpu():
        return eps_nearest_core_pallas(q, x, labels, core)
    if _force_pallas():
        return eps_nearest_core_pallas(
            q, x, labels, core, bq=64, bn=64, interpret=True
        )
    return ref.eps_nearest_core_ref(q, x, labels, core)


def knn_topk(q: Array, x: Array, *, k: int) -> tuple[Array, Array]:
    """Fused streaming distance + top-k (values ascending, indices)."""
    if _on_tpu():
        return knn_topk_pallas(q, x, k=k)
    if _force_pallas():
        return knn_topk_pallas(q, x, k=k, bq=32, bn=64, interpret=True)
    return ref.knn_topk_ref(q, x, k)


def bucket_scan_prepad(
    bucket_x: Array, bucket_ids: Array, scale: Array | None = None
) -> tuple[Array, Array, Array | None]:
    """Apply ``bucket_scan_topk``'s padding policy once, at upload time.

    Looping callers (core/knn.py's while-loop) pre-pad the datastore-sized
    operands here so the defensive per-step pads inside the kernel wrapper
    are no-ops instead of a full-datastore copy per step.  Identity on the
    jnp-reference path (no tiling there).
    """
    if _on_tpu():
        return prepad_buckets(bucket_x, bucket_ids, scale, interpret=False)
    if _force_pallas():
        return prepad_buckets(bucket_x, bucket_ids, scale, interpret=True)
    return bucket_x, bucket_ids, scale


def bucket_scan_topk(
    q: Array,
    bucket_x: Array,
    bucket_ids: Array,
    bsel: Array,
    act: Array,
    top_d: Array,
    top_i: Array,
    scale: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Fused forest-scan step: gather ``bsel`` buckets, distances, top-k merge.

    Returns the merged (top_d, top_i) and each query's (Q,) count of
    candidates that entered its top-k.  See kernels/bucket_scan.py for the
    kernel and kernels/ref.py for the oracle.  ``scale`` enables the int8
    bucket storage path.
    """
    if _on_tpu():
        return bucket_scan_topk_pallas(q, bucket_x, bucket_ids, bsel, act, top_d, top_i, scale)
    if _force_pallas():
        return bucket_scan_topk_pallas(
            q, bucket_x, bucket_ids, bsel, act, top_d, top_i, scale, interpret=True
        )
    return ref.bucket_scan_topk_ref(q, bucket_x, bucket_ids, bsel, act, top_d, top_i, scale)


# The streaming delta phase dispatches through the identical kernel step —
# named so call sites (core/knn.py STEP 2c) read as what they scan.
delta_scan_topk = bucket_scan_topk


def quantize_datastore(x: Array) -> tuple[Array, Array]:
    """Symmetric per-row int8 quantization for the retrieval datastore."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1), 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    return xq, scale.astype(jnp.float32)
