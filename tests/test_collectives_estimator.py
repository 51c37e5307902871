"""Collective helpers (shard_map islands) + HBM-traffic estimator tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.distributed.collectives import (
    compressed_psum,
    ring_allgather_pipelined,
    topk_allgather_merge,
)
from repro.distributed.estimator import estimate_memory_bytes
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def mesh1d():
    return make_host_mesh()


def _run_island(mesh, fn, *args, in_specs=None, out_specs=P()):
    n = len(jax.devices())
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=in_specs or tuple(P() for _ in args),
        out_specs=out_specs, check_vma=False,
    )(*args)


def test_compressed_psum_matches_fp32(mesh1d, rng):
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    got = _run_island(mesh1d, lambda a: compressed_psum(a, "data"), x)
    want = _run_island(mesh1d, lambda a: jax.lax.psum(a, "data"), x)
    # single value per shard (replicated input): compression error ~ bf16 eps
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2)
    assert got.dtype == jnp.float32  # wire dtype restored


def test_ring_allgather_pipelined_matches_plain(mesh1d, rng):
    n = len(jax.devices())
    x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

    def island(a):
        plain = jax.lax.all_gather(a, "data", axis=0, tiled=True)
        chunked = ring_allgather_pipelined(a, "data", chunks=4)
        return plain, chunked

    plain, chunked = _run_island(
        mesh1d, island, x,
        in_specs=(P("data"),) if n > 1 else (P(),),
        out_specs=(P(), P()),
    )
    np.testing.assert_allclose(np.asarray(plain), np.asarray(chunked), atol=1e-6)


def test_topk_allgather_merge(mesh1d, rng):
    vals = jnp.asarray(np.sort(rng.normal(size=(4, 3)), axis=1), jnp.float32)
    payload = jnp.asarray(rng.integers(0, 100, (4, 3)), jnp.int32)

    def island(v, p):
        return topk_allgather_merge(v, p, "data", k=3)

    got_v, got_p = _run_island(mesh1d, island, vals, payload,
                               out_specs=(P(), P()))
    # replicated input: global top-k == local top-k
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(vals), atol=1e-6)


def test_estimator_terms_positive_and_ordered():
    """Every shape kind produces positive totals; decode dominated by
    params+cache; train dominated by activations at these scales."""
    cfg = get_config("granite-20b")
    mesh = make_host_mesh()

    train = estimate_memory_bytes(cfg, SHAPES["train_4k"], mesh,
                                  params_local=int(1e9), opt_local=int(1e8))
    decode = estimate_memory_bytes(cfg, SHAPES["decode_32k"], mesh,
                                   params_local=int(1e9), cache_local=int(5e8),
                                   datastore_local=int(1e7))
    prefill = estimate_memory_bytes(cfg, SHAPES["prefill_32k"], mesh,
                                    params_local=int(1e9), cache_local=int(5e8))
    for parts in (train, decode, prefill):
        assert parts["total"] > 0
        assert all(v >= 0 for v in parts.values())
    assert train["layer_working_set"] > train["params"] * 0.01
    assert decode["params"] + decode["cache"] >= 0.9 * (
        decode["total"] - decode["datastore"] - decode["activations"])
