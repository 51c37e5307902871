"""Retrieval-layer microbench (framework feature built on the paper's
index): kNN-LM datastore scan throughput — flat vs forest-pruned vs int8
quantized — over a synthetic embedding datastore."""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Timer, emit
from repro.compile_cache import enable_compile_cache
from repro.api import Config, IndexConfig, OverlapIndex
from repro.data.synthetic import embedding_datastore
from repro.kernels import ops as kops


def run(full: bool = False, out: dict | None = None) -> None:
    n = 200_000 if full else 30_000
    dim, k, n_q = 256, 8, 64
    keys, values = embedding_datastore(n, dim)
    g = np.random.default_rng(3)
    q = keys[g.choice(n, n_q)] + 0.1 * g.normal(size=(n_q, dim)).astype(np.float32)
    qj = jnp.asarray(q)
    kj = jnp.asarray(keys)

    # flat fused scan
    kops.knn_topk(qj[:2], kj, k=k)  # warm
    with Timer() as t:
        d_flat, i_flat = kops.knn_topk(qj, kj, k=k)
        d_flat.block_until_ready()
    emit("retrieval/flat", t.s * 1e6 / n_q, f"n={n};dim={dim};k={k}")

    # int8 quantized scan
    xq, scale = kops.quantize_datastore(kj)
    kops.pairwise_sq_l2_int8(qj[:2], xq, scale)
    with Timer() as t:
        d2 = kops.pairwise_sq_l2_int8(qj, xq, scale)
        dq, iq = jnp.sort(d2, axis=1)[:, :k], jnp.argsort(d2, axis=1)[:, :k]
        dq.block_until_ready()
    agree = float(np.mean([
        len(set(np.asarray(iq)[i].tolist()) & set(np.asarray(i_flat)[i].tolist())) / k
        for i in range(n_q)]))
    emit("retrieval/int8", t.s * 1e6 / n_q,
         f"n={n};dim={dim};k={k};agree_vs_f32={agree:.3f};bytes_ratio=0.25")

    # paper's forest index (pruned scan) through the facade
    cfg = Config(index=IndexConfig(
        method="vbm", eps=3.5, min_pts=8, xi_min=0.4, xi_max=0.8,
        dbscan_block=2048,
    ))
    ix = OverlapIndex.build(keys, cfg)
    ix.search(q, k=k, mode="forest")  # warm the plan
    with Timer() as t:
        res = ix.search(q, k=k, mode="forest")
    recall = float(np.mean([
        len(set(res.ids[i].tolist()) & set(np.asarray(i_flat)[i].tolist())) / k
        for i in range(n_q)]))
    frac = float(res.stats["distances"].mean()) / n
    emit("retrieval/forest-vbm", t.s * 1e6 / n_q,
         f"n={n};k={k};indexes={ix.build_report.n_indexes};"
         f"dist_frac={frac:.4f};recall_vs_exact={recall:.3f}")
    if out is not None:
        out["forest_dist_frac"] = frac
        out["forest_recall"] = recall


if __name__ == "__main__":
    enable_compile_cache()
    run()
