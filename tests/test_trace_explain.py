"""Per-query tracing, overlap-attribution explain, and metrics export.

Covers the observability tentpole end to end: TraceContext propagation and
``Trace.reconstruct`` round trips (index searches AND multi-request serving
runs), ``OverlapIndex.explain`` attribution — conservation against
``SearchStats.buckets_visited`` and bitwise identity with plain search —
the measured-waste maintenance trigger, event-log rotation, and the
Prometheus/CLI export surface.
"""
import json
import math

import numpy as np
import pytest

from repro.api import (
    Config,
    IndexConfig,
    ObsConfig,
    OverlapIndex,
    StreamConfig,
)
from repro.obs import (
    EventLog,
    Registry,
    Trace,
    TraceContext,
    TraceSampler,
    current_trace,
    new_trace,
    use_trace,
)
from repro.obs import export as obs_export
from repro.obs.attribution import attribute_visits


def _cfg(**kw) -> Config:
    obs_kw = {k: kw.pop(k) for k in list(kw)
              if k in ("trace_sample", "events_path", "events_max_bytes",
                       "events_backups", "enabled")}
    stream_kw = {"capacity": 64, **{k: kw.pop(k) for k in list(kw)
                                    if k in ("wasted_rebuild", "fill_rebuild")}}
    assert not kw, kw
    return Config(
        index=IndexConfig(
            method="vbm", eps=1.5, min_pts=8, xi_min=0.3, xi_max=0.7
        ),
        stream=StreamConfig(**stream_kw),
        obs=ObsConfig(**obs_kw),
    )


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------


def test_trace_context_ids_and_parentage():
    ctx = TraceContext("abc")
    assert ctx.root_id == "abc.1"
    s1, p1 = ctx.push()
    assert (s1, p1) == ("abc.2", "abc.1")
    s2, p2 = ctx.push()  # nests under s1
    assert (s2, p2) == ("abc.3", "abc.2")
    l1, lp = ctx.link()  # point event parented at the open span, no push
    assert lp == s2 and l1 == "abc.4"
    ctx.pop()
    s3, p3 = ctx.push()  # back at depth 1 -> parents to s1 again
    assert p3 == s1
    ctx.pop()
    ctx.pop()
    _, p4 = ctx.push()  # empty stack -> parents to the root
    assert p4 == ctx.root_id


def test_use_trace_ambient_and_noop():
    assert current_trace() is None
    ctx = new_trace()
    with use_trace(ctx):
        assert current_trace() is ctx
        # None is a true no-op: the outer context stays ambient
        with use_trace(None):
            assert current_trace() is ctx
        # unsampled contexts are never installed
        with use_trace(new_trace(sampled=False)):
            assert current_trace() is ctx
    assert current_trace() is None


def test_sampler_is_deterministic_and_exact():
    s = TraceSampler(0.25)
    admitted = [i for i in range(100) if s.sample()]
    assert len(admitted) == 25
    # systematic: every 4th request, reproducibly
    assert admitted == [i for i in range(3, 100, 4)]
    assert TraceSampler(0.0).maybe_trace() is None
    assert all(TraceSampler(1.0).sample() for _ in range(10))
    with pytest.raises(ValueError, match="rate"):
        TraceSampler(1.5)


def test_registry_spans_join_ambient_trace(tmp_path):
    p = tmp_path / "ev.jsonl"
    reg = Registry(events=EventLog(str(p)))
    ctx = new_trace()
    with use_trace(ctx):
        with reg.span("outer"):
            with reg.span("inner"):
                reg.emit_event({"event": "note"}, traced_only=True)
        reg.record_span("external_wait", 0.5)
        reg.emit_trace_root(ctx, "request", 1.0)
    with reg.span("untraced"):
        pass
    reg.emit_event({"event": "dropped"}, traced_only=True)  # no ambient trace
    recs = EventLog.read(str(p))
    by_span = {r.get("span", r.get("event")): r for r in recs}
    assert "dropped" not in by_span
    root = by_span["request"]
    assert root["span_id"] == ctx.root_id and root["parent_id"] is None
    assert by_span["outer"]["parent_id"] == ctx.root_id
    assert by_span["outer/inner"]["parent_id"] == by_span["outer"]["span_id"]
    assert by_span["note"]["parent_id"] == by_span["outer/inner"]["span_id"]
    assert by_span["external_wait"]["parent_id"] == ctx.root_id
    assert by_span["external_wait"]["dur_s"] == 0.5
    assert "trace_id" not in by_span["untraced"]
    # the tree reassembles: one root, everything under it
    t = Trace.reconstruct(str(p), ctx.trace_id)
    assert [r.name for r in t.roots] == ["request"]
    assert t.span_names() == {"request", "outer", "outer/inner", "note",
                              "external_wait"}
    assert "request" in t.render()


# ---------------------------------------------------------------------------
# event-log rotation
# ---------------------------------------------------------------------------


def test_event_log_rotation_keeps_backups_and_read_spans(tmp_path):
    p = str(tmp_path / "rot.jsonl")
    log = EventLog(p, max_bytes=120, backups=2)
    for i in range(40):
        log.emit({"event": "x", "i": i})
    log.close()
    files = EventLog.rotated_paths(p)
    assert files == [f"{p}.2", f"{p}.1", p]
    recs = EventLog.read(p)
    seq = [r["i"] for r in recs]
    # oldest rotations fell off the end; what remains is contiguous,
    # oldest-first, and ends at the newest event
    assert seq == sorted(seq) and seq[-1] == 39
    assert len(seq) < 40


def test_event_log_rotation_zero_backups_truncates(tmp_path):
    p = str(tmp_path / "zero.jsonl")
    log = EventLog(p, max_bytes=100, backups=0)
    for i in range(30):
        log.emit({"event": "x", "i": i})
    log.close()
    assert EventLog.rotated_paths(p) == [p]
    seq = [r["i"] for r in EventLog.read(p)]
    assert seq == sorted(seq) and seq[-1] == 29 and len(seq) < 30


def test_event_log_single_event_never_splits(tmp_path):
    # a record larger than max_bytes still lands whole in one file
    p = str(tmp_path / "big.jsonl")
    log = EventLog(p, max_bytes=16, backups=1)
    log.emit({"event": "huge", "payload": "y" * 100})
    log.emit({"event": "next"})
    log.close()
    recs = EventLog.read(p)
    assert [r["event"] for r in recs] == ["huge", "next"]


def test_event_log_rotation_validation(tmp_path):
    with pytest.raises(ValueError, match="max_bytes"):
        EventLog(str(tmp_path / "a.jsonl"), max_bytes=0)
    with pytest.raises(ValueError, match="backups"):
        EventLog(str(tmp_path / "b.jsonl"), backups=-1)


# ---------------------------------------------------------------------------
# explain: attribution semantics
# ---------------------------------------------------------------------------


def test_attribute_visits_hand_case():
    # 2 indexes; buckets: row0 (idx 0) holds ids {0,1}, row1 (idx 1) holds
    # {2}, row2 (idx 1) holds {3}.  Query 0 visited rows [0, 2] and kept
    # ids {0, 1}: row0 contributed, row2 (owned by 1, home 0) was wasted.
    rep = attribute_visits(
        order=np.array([[0, 2, 1]]),
        visits=np.array([[2]]),
        dorder=None,
        dvisits=None,
        result_ids=np.array([[0, 1]]),
        home=np.array([0]),
        n_indexes=2,
        bucket_index=np.array([0, 1, 1]),
        bucket_ids=np.array([[0, 1], [2, -1], [3, -1]]),
        bucket_mask=np.array([[True, True], [True, False], [True, False]]),
        main_rows_per_shard=3,
        rates=np.array([[0.0, 0.4], [0.4, 0.0]]),
        method="vbm",
    )
    assert rep.contributing.tolist() == [1]
    assert rep.wasted.tolist() == [1]
    assert rep.wasted_pair[1, 0] == 1 and rep.wasted_pair.sum() == 1
    assert rep.visited_pair[0, 0] == 1 and rep.visited_pair[1, 0] == 1
    assert rep.wasted_fraction == 0.5
    top = rep.top_pairs()
    assert top[0] == {"visited": 1, "home": 0, "wasted": 1, "visits": 1,
                      "rate": 0.4}
    assert json.dumps(rep.to_dict())


@pytest.fixture(scope="module")
def explained(blob_data):
    """One index + queries + (search, explain) results, with delta phase."""
    ix = OverlapIndex.build(blob_data, _cfg())
    g = np.random.default_rng(5)
    ix.ingest(
        (blob_data[g.choice(len(blob_data), 48)]
         + 0.1 * g.normal(size=(48, blob_data.shape[1]))).astype(np.float32)
    )
    q = np.asarray(blob_data[g.choice(len(blob_data), 24)])
    return ix, q, ix.search(q, k=6), ix.explain(q, k=6)


def test_explain_conservation_and_bitwise(explained):
    ix, q, res, rep = explained
    # bitwise: the explain plan runs the identical op sequence
    np.testing.assert_array_equal(rep.result.dists, res.dists)
    np.testing.assert_array_equal(rep.result.ids, res.ids)
    # conservation: every visit is contributing XOR wasted, per query
    np.testing.assert_array_equal(
        rep.contributing + rep.wasted, res.stats["buckets_visited"]
    )
    assert rep.queries == len(q)
    assert (rep.home >= 0).all() and (rep.home < ix.n_indexes).all()
    # pair matrices cover exactly the visits attributed to real indexes
    assert rep.visited_pair.sum() <= rep.total_visits
    assert rep.wasted_pair.sum() <= rep.wasted.sum()
    assert 0.0 <= rep.wasted_fraction <= 1.0
    # a clustered query set finds most answers near home: some contribution
    assert rep.contributing.sum() > 0


def test_explain_separate_plan_leaves_search_plan_alone(explained):
    ix, q, res, rep = explained
    assert rep.result.plan.key.explain is True
    assert res.plan.key.explain is False
    assert rep.result.plan is not res.plan
    # plan cache keeps both compiled executors; repeat calls re-use them
    before = ix.plans.stats()["misses"]
    ix.search(q, k=6)
    ix.explain(q, k=6)
    assert ix.plans.stats()["misses"] == before


def test_explain_metrics_rollup(explained):
    ix, q, res, rep = explained
    m = ix.metrics()
    oh = m["overlap_health"]
    assert oh["explained_queries"] >= len(q)
    assert oh["contributing"] >= int(rep.contributing.sum())
    assert oh["wasted"] >= int(rep.wasted.sum())
    assert 0.0 <= oh["wasted_fraction"] <= 1.0
    total_pairs = sum(oh["wasted_pairs"].values())
    assert total_pairs == sum(
        v for (n, _), v in ix.obs.counters().items()
        if n == "explain.wasted_pair"
    )
    # monitor received the evidence (delta exists -> monitor exists)
    assert oh["monitor_wasted_share"] is not None
    assert json.dumps(m["overlap_health"])


def test_wasted_trigger_fires_and_resets(blob_data):
    ix = OverlapIndex.build(blob_data, _cfg(wasted_rebuild=0.05))
    g = np.random.default_rng(6)
    ix.ingest(
        (blob_data[g.choice(len(blob_data), 32)]
         + 0.1 * g.normal(size=(32, blob_data.shape[1]))).astype(np.float32)
    )
    # far-flung queries waste visits across every index they touch
    q = g.uniform(-15, 15, size=(32, blob_data.shape[1])).astype(np.float32)
    rep = ix.explain(q, k=5)
    share = ix.monitor.wasted_share()
    assert (ix.monitor.attr_visits >= 0).all()
    report = ix.check()
    fired = [i for i, why in report.reasons.items() if "wasted" in why]
    expect = [
        i for i in range(ix.n_indexes)
        if ix.monitor.attr_visits[i] >= ix.monitor.WASTED_MIN_VISITS
        and share[i] >= 0.05
    ]
    assert fired == expect
    assert expect, "waste evidence should fire the trigger in this setup"
    # a maintain() rebuild recreates the monitor -> accumulators reset, the
    # measured-waste trigger cannot re-fire off stale evidence
    ix.maintain()
    assert ix.monitor.attr_visits.sum() == 0
    assert not any(
        "wasted" in why for why in ix.check().reasons.values()
    )


def test_explain_without_monitor_or_delta(blob_data):
    ix = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    rep = ix.explain(q, k=4)  # no ingest: no delta, no monitor
    res = ix.search(q, k=4)
    np.testing.assert_array_equal(rep.result.ids, res.ids)
    np.testing.assert_array_equal(
        rep.contributing + rep.wasted, res.stats["buckets_visited"]
    )
    assert ix.metrics()["overlap_health"]["monitor_wasted_share"] is None


# ---------------------------------------------------------------------------
# trace propagation through the index + reconstruction
# ---------------------------------------------------------------------------


def test_search_self_sampling_tracing(blob_data, tmp_path):
    p = str(tmp_path / "ix.jsonl")
    ix = OverlapIndex.build(blob_data, _cfg(
        trace_sample=0.5, events_path=p,
    ))
    q = np.asarray(blob_data[:4])
    for _ in range(6):
        ix.search(q, k=3)
    tids = Trace.trace_ids(p)
    assert len(tids) == 3  # deterministic: every 2nd search
    t = Trace.reconstruct(p, tids[0])
    # one root ("search" — its synthesized parent id is never emitted),
    # with the per-phase spans and the per-island point event beneath it
    assert len(t.roots) == 1 and t.roots[0].name == "search"
    names = t.span_names()
    assert {"search", "search/plan_lookup", "search/dispatch",
            "search/device_wait", "search/copy_back", "search/record",
            "island"} <= names
    # untraced searches still recorded their spans, unlinked
    unlinked = [r for r in EventLog.read(p)
                if r.get("span") == "search" and "trace_id" not in r]
    assert len(unlinked) == 3


def test_search_explicit_trace_joins_caller_tree(blob_data, tmp_path):
    p = str(tmp_path / "joined.jsonl")
    ix = OverlapIndex.build(blob_data, _cfg(events_path=p))
    ctx = new_trace()
    ix.search(np.asarray(blob_data[:4]), k=3, trace=ctx)
    t = Trace.reconstruct(p, ctx.trace_id)
    assert len(t.roots) == 1
    assert t.roots[0].record["parent_id"] == ctx.root_id
    assert "search/dispatch" in t.span_names()


def test_tracing_off_emits_no_linkage(blob_data, tmp_path):
    p = str(tmp_path / "off.jsonl")
    ix = OverlapIndex.build(blob_data, _cfg(events_path=p))  # sample 0.0
    ix.search(np.asarray(blob_data[:4]), k=3)
    assert Trace.trace_ids(p) == []


def test_serving_run_reconstructs_per_request_trees(tmp_path):
    import jax

    from repro.configs import get_smoke_config
    from repro.configs.base import RetrievalConfig
    from repro.data.synthetic import embedding_datastore
    from repro.models.model import Model
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.retrieval import build_flat_datastore

    cfg = get_smoke_config("qwen2-0.5b").replace(
        retrieval=RetrievalConfig(enabled=True, k=4, lam=0.5,
                                  temperature=1.0, datastore_size=512))
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    keys, values = embedding_datastore(256, cfg.d_model, seed=3)
    ds = build_flat_datastore(keys, values % cfg.vocab_size)
    p = str(tmp_path / "serve.jsonl")
    reg = Registry(events=EventLog(p))
    engine = ServeEngine(model, params, num_slots=2, max_len=32,
                         datastore=ds, registry=reg, trace_sample=1.0)
    g = np.random.default_rng(0)
    reqs = [Request(rid=rid,
                    prompt=g.integers(0, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=3)
            for rid in range(5)]
    for r in reqs:
        engine.submit(r)
    finished = engine.run()
    assert len(finished) == 5
    # every request got its own trace; each reassembles into one tree
    # rooted at the request with queue wait + prefill beneath it
    tids = Trace.trace_ids(p)
    assert len(tids) == 5
    assert {r.trace.trace_id for r in reqs} == set(tids)
    for tid in tids:
        t = Trace.reconstruct(p, tid)
        assert len(t.roots) == 1
        assert t.roots[0].name == "serve.request_latency_s"
        assert t.roots[0].dur_s > 0.0
        assert {"serve.queue_wait", "serve.prefill"} <= t.span_names()
    # sampled-off engines keep the latency histogram behavior
    assert reg.snapshot()["histograms"]["serve.request_latency_s"]["count"] == 5


# ---------------------------------------------------------------------------
# export surface
# ---------------------------------------------------------------------------


def test_prometheus_render_parse_roundtrip(blob_data):
    ix = OverlapIndex.build(blob_data, _cfg())
    q = np.asarray(blob_data[:8])
    ix.search(q, k=5)
    ix.explain(q, k=5)
    text = ix.obs.to_prometheus()
    samples = obs_export.parse_prometheus(text)  # raises on malformed output
    assert samples, "expected at least one sample"
    by_name = {s["name"]: s for s in samples}
    assert "search_queries" in by_name
    assert by_name["search_queries"]["value"] == 16.0
    # histograms render as summaries with quantiles + sum/count
    assert any(s["name"] == "search" and s["labels"].get("quantile") == "0.5"
               for s in samples)
    assert "search_count" in by_name and by_name["search_count"]["value"] >= 1
    # island counters carry their labels through
    island = [s for s in samples
              if s["name"].startswith("search_island_buckets_visited")]
    assert island and all("island" in s["labels"] for s in island)


def test_prometheus_parser_rejects_garbage():
    with pytest.raises(ValueError, match="line 1"):
        obs_export.parse_prometheus("not a metric line\n")


def test_prometheus_nonfinite_values():
    reg = Registry()
    reg.gauge("g").set(math.inf)
    reg.histogram("h")  # registered but never observed -> NaN percentiles
    samples = obs_export.parse_prometheus(reg.to_prometheus())
    gauges = [s for s in samples if s["name"] == "g"]
    assert gauges and gauges[0]["value"] == math.inf
    p50 = [s for s in samples
           if s["name"] == "h" and s["labels"].get("quantile") == "0.5"]
    assert p50 and math.isnan(p50[0]["value"])
    count = [s for s in samples if s["name"] == "h_count"]
    assert count and count[0]["value"] == 0.0


def test_export_cli_check_and_snapshot(blob_data, tmp_path, capsys):
    p = str(tmp_path / "cli.jsonl")
    ix = OverlapIndex.build(blob_data, _cfg(
        events_path=p, trace_sample=1.0,
    ))
    ix.search(np.asarray(blob_data[:4]), k=3)
    snap_path = tmp_path / "metrics.json"
    snap_path.write_text(json.dumps(ix.metrics()))

    assert obs_export.main(["--events", p, "--check"]) == 0
    out = capsys.readouterr().out
    assert "prometheus render OK" in out
    assert "search/dispatch" in out  # span latency table

    assert obs_export.main(["--snapshot", str(snap_path),
                            "--format", "prometheus"]) == 0
    out = capsys.readouterr().out
    obs_export.parse_prometheus(out)

    assert obs_export.main(["--events", p, "--traces"]) == 0
    tid = capsys.readouterr().out.strip().splitlines()[0]
    assert obs_export.main(["--events", p, "--trace", tid]) == 0
    assert "search" in capsys.readouterr().out
    assert obs_export.main(["--events", p, "--trace", "nope"]) == 1
    capsys.readouterr()


def test_export_cli_events_from_env(tmp_path, monkeypatch, capsys):
    p = str(tmp_path / "env.jsonl")
    with EventLog(p) as log:
        reg = Registry(events=log)
        with reg.span("phase"):
            pass
    monkeypatch.setenv("REPRO_OBS_EVENTS", p)
    assert obs_export.main(["--check"]) == 0
    assert "phase" in capsys.readouterr().out
