"""Retrieval layer (kNN-LM) + serving engine tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import RetrievalConfig
from repro.data.synthetic import embedding_datastore
from repro.models.model import Model
from repro.serve.engine import IngestRequest, Request, ServeEngine
from repro.serve.retrieval import (
    build_flat_datastore,
    build_forest_datastore,
    knn_interpolate,
    knn_logits,
)


@pytest.fixture(scope="module")
def retrieval_cfg():
    return get_smoke_config("qwen2-0.5b").replace(
        retrieval=RetrievalConfig(enabled=True, k=4, lam=0.5,
                                  temperature=1.0, datastore_size=512))


def test_knn_logits_distribution(retrieval_cfg, rng):
    cfg = retrieval_cfg
    keys, values = embedding_datastore(512, cfg.d_model, seed=0)
    values = values % cfg.vocab_size
    ds = build_flat_datastore(keys, values)
    hidden = jnp.asarray(keys[:6] + 0.01 * rng.normal(size=(6, cfg.d_model)),
                         jnp.float32)
    p = knn_logits(hidden, ds, cfg)
    assert p.shape == (6, cfg.padded_vocab)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, rtol=1e-4)
    # query sitting on a datastore key must put most mass on its token
    top = np.asarray(jnp.argmax(p, axis=-1))
    assert (top == np.asarray(values[:6])).mean() >= 0.5


def test_knn_interpolate_mixes(retrieval_cfg):
    cfg = retrieval_cfg
    rng = np.random.default_rng(11)  # order-independent stream
    keys, values = embedding_datastore(256, cfg.d_model, seed=1)
    values = values % cfg.vocab_size
    ds = build_flat_datastore(keys, values)
    logits = jnp.asarray(rng.normal(size=(3, cfg.padded_vocab)), jnp.float32)
    hidden = jnp.asarray(keys[:3], jnp.float32)
    out = knn_interpolate(logits, hidden, ds, cfg)
    assert out.shape == logits.shape
    p = np.exp(np.asarray(out))
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-3)
    # lam=0 must reduce to the LM distribution
    cfg0 = cfg.replace(retrieval=cfg.retrieval.__class__(
        enabled=True, k=4, lam=0.0, temperature=1.0, datastore_size=512))
    out0 = knn_interpolate(logits, hidden, ds, cfg0)
    np.testing.assert_allclose(  # one f32 ulp at |logit|~8 is ~1e-6
        np.asarray(jax.nn.log_softmax(logits)), np.asarray(out0), atol=5e-6)


def test_quantized_datastore_agrees(rng):
    cfg = get_smoke_config("qwen2-0.5b").replace(
        retrieval=RetrievalConfig(enabled=True, k=4, datastore_size=512))
    keys, values = embedding_datastore(512, cfg.d_model, seed=2)
    values = values % cfg.vocab_size
    ds32 = build_flat_datastore(keys, values)
    ds8 = build_flat_datastore(keys, values, quantized=True)
    hidden = jnp.asarray(keys[:8], jnp.float32)
    p32 = np.asarray(jnp.argmax(knn_logits(hidden, ds32, cfg), -1))
    p8 = np.asarray(jnp.argmax(knn_logits(hidden, ds8, cfg), -1))
    assert (p32 == p8).mean() >= 0.75  # int8 keeps neighbor structure


def test_engine_serves_batched_requests(retrieval_cfg, rng):
    cfg = retrieval_cfg
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    keys, values = embedding_datastore(256, cfg.d_model, seed=3)
    ds = build_flat_datastore(keys, values % cfg.vocab_size)
    engine = ServeEngine(model, params, num_slots=2, max_len=32, datastore=ds)
    for rid in range(5):
        engine.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
            max_new_tokens=5))
    finished = engine.run()
    assert len(finished) == 5
    for r in finished:
        assert len(r.out_tokens) >= 5
        assert all(0 <= t < cfg.padded_vocab for t in r.out_tokens)
    # continuous batching actually reused slots (5 reqs > 2 slots)
    assert engine.steps >= 8


def test_engine_mixed_query_ingest_traffic(retrieval_cfg, rng):
    """One engine serves interleaved decode requests and datastore inserts:
    the IoT read+write pattern.  Ingested pairs must become retrievable by
    the very same engine (datastore is a traced argument, not a baked-in
    closure constant)."""
    cfg = retrieval_cfg
    model = Model(cfg)
    params = model.init(jax.random.key(2))
    keys, values = embedding_datastore(256, cfg.d_model, seed=4)
    ds = build_forest_datastore(keys, values % cfg.vocab_size, stream_capacity=64)
    engine = ServeEngine(model, params, num_slots=2, max_len=32, datastore=ds)

    new_keys = (-keys[:12] + 40.0).astype(np.float32)  # far from main keys
    new_vals = np.full(12, 9, np.int32)
    for rid in range(4):
        engine.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
            max_new_tokens=4))
        engine.submit(IngestRequest(
            rid=100 + rid, keys=new_keys[rid * 3:(rid + 1) * 3],
            values=new_vals[rid * 3:(rid + 1) * 3]))
    finished = engine.run()

    decodes = [r for r in finished if isinstance(r, Request)]
    ingests = [r for r in finished if isinstance(r, IngestRequest)]
    assert len(decodes) == 4 and len(ingests) == 4
    assert all(r.done for r in ingests)
    assert sum(r.accepted for r in ingests) == 12
    assert int(np.asarray(engine.datastore.delta.count).sum()) == 12
    for r in decodes:
        assert len(r.out_tokens) >= 4
        assert all(0 <= t < cfg.padded_vocab for t in r.out_tokens)
    # the streamed pairs are live in the SAME engine's retrieval path
    p = knn_logits(jnp.asarray(new_keys[:4]), engine.datastore, cfg)
    assert (np.asarray(jnp.argmax(p, -1)) == 9).all()


def test_engine_fails_single_ingest_not_the_run_loop(rng):
    """An IngestRequest against a non-streaming datastore fails with an
    error ack; in-flight decode requests still complete."""
    cfg = get_smoke_config("smollm-135m")
    model = Model(cfg)
    params = model.init(jax.random.key(3))
    engine = ServeEngine(model, params, num_slots=1, max_len=24)  # no datastore
    engine.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, 4)
                          .astype(np.int32), max_new_tokens=3))
    engine.submit(IngestRequest(rid=1, keys=np.zeros((2, 4), np.float32),
                                values=np.zeros(2, np.int32)))
    finished = engine.run()
    ing = next(r for r in finished if isinstance(r, IngestRequest))
    dec = next(r for r in finished if isinstance(r, Request))
    assert ing.done and ing.accepted == 0 and ing.error
    assert len(dec.out_tokens) >= 3


def test_ingest_keys_never_outruns_values_tail(retrieval_cfg):
    """Regression: ids are issued from the datastore's own high-water mark
    and stop at the preallocated tail, so an accepted streamed key can never
    read a clipped/foreign token value."""
    from repro.serve.retrieval import ingest_keys

    cfg = retrieval_cfg
    keys, values = embedding_datastore(256, cfg.d_model, seed=6)
    ds = build_forest_datastore(keys, values % cfg.vocab_size, stream_capacity=8)
    g = np.random.default_rng(8)
    new_keys = (-keys[:16] + 40.0).astype(np.float32)
    new_vals = (np.arange(16) + 100).astype(np.int32)
    ds, acc1 = ingest_keys(ds, new_keys, new_vals)
    assert acc1 == 8  # tail exhausted exactly at stream_capacity
    ds, acc2 = ingest_keys(ds, new_keys[8:], new_vals[8:])
    assert acc2 == 0  # refused up front, nothing corrupted
    assert int(ds.next_id) == ds.n_main + 8
    # every accepted key retrieves ITS token, not a clipped neighbor's
    p = knn_logits(jnp.asarray(new_keys[:8]), ds, cfg)
    np.testing.assert_array_equal(np.asarray(jnp.argmax(p, -1)), new_vals[:8])


def test_engine_greedy_matches_manual_decode(rng):
    """Engine output must equal a hand-rolled prefill+decode loop."""
    cfg = get_smoke_config("smollm-135m")
    model = Model(cfg)
    params = model.init(jax.random.key(1))
    prompt = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)

    engine = ServeEngine(model, params, num_slots=1, max_len=24)
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    got = engine.run()[0].out_tokens

    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                  max_len=24)
    want = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(5):
        lg, cache = model.decode_step(
            params, jnp.asarray([[want[-1]]], jnp.int32), cache, jnp.int32(pos))
        want.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert got == want


def test_tokens_do_not_depend_on_slot_count():
    """The engine oracle at the published smollm-135m widths (depth cut to
    2 layers, datastore to 16,384 keys): each request's tokens from a
    4-slot engine equal a 1-slot engine's.  A one-row decode batch would
    round differently and flip near-tied kNN-LM argmaxes here."""
    from repro.configs.smollm_135m import CONFIG

    cfg = CONFIG.replace(
        num_layers=2, retrieval=RetrievalConfig(enabled=True, datastore_size=16_384)
    )
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    keys, values = embedding_datastore(16_384, cfg.d_model, seed=0)
    ds = build_flat_datastore(keys, values % cfg.vocab_size)
    prompts = np.random.default_rng(2).integers(0, cfg.vocab_size, (8, 16))

    def serve(slots):
        engine = ServeEngine(model, params, num_slots=slots, max_len=33, datastore=ds)
        for i, p in enumerate(prompts):
            engine.submit(Request(rid=i, prompt=p.astype(np.int32), max_new_tokens=16))
        return [r.out_tokens for r in sorted(engine.run(), key=lambda r: r.rid)]

    assert serve(4) == serve(1)
