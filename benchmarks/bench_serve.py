"""Open-loop saturation bench for the serving front (serve/engine.py).

Drives offered-QPS sweeps through ``ServeEngine`` the way a network front
would: arrivals are a seeded Poisson process scheduled on the wall clock,
INDEPENDENT of completions (open loop — offered load does not back off
when the engine falls behind, which is exactly what exposes the
saturation knee).  Each operating point runs twice:

  * ``noshed`` — no deadlines: every request is admitted and served, so
    past the knee the queue (and the p99 of everything in it) grows with
    offered load;
  * ``shed``   — every request carries the same ``deadline_s`` budget;
    admission control rejects on submit when the projected wait exceeds
    it, queued requests expire before prefill, and mid-flight requests
    are evicted — so the p99 of ADMITTED requests stays bounded near the
    deadline while the shed rate absorbs the overload.

Offered QPS points are calibrated to the measured engine capacity
(``num_slots / (max_new_tokens * decode_step_s)``), so the same ratios
(0.5x .. 5x capacity) land on both a laptop and a CI runner.

Everything reported comes straight out of ``ServeEngine.metrics()`` (the
repro.obs registry): request-latency percentiles are the engine's own
``serve.request_latency_s`` histogram (completed requests only — shed
waits live in ``serve.shed_wait_s``), shed counts are the
``serve.shed{reason=...}`` counters, and mean slot occupancy is
``serve.tokens / (serve.steps * num_slots)``.  With ``$REPRO_OBS_EVENTS``
set, a fraction of requests is trace-sampled and the slowest completed
sampled request of the heaviest shed point is reconstructed and printed —
*where* a tail request spent its time (queue wait vs prefill vs decode).

Hard gates (exit non-zero), the acceptance criteria of the serving front:
  * with shedding, p99 of admitted requests stays bounded
    (<= deadline + a small service allowance) at EVERY offered-QPS point,
    including far past the knee;
  * at the heaviest point the no-shedding p99 exceeds the shedding p99
    (the unbounded queue is visible) and the shed rate is non-zero;
  * per point, ``submitted == completed + shed`` once drained.

Artifacts: CSV lines on stdout (benchmarks/common.emit) and
BENCH_serve.json (common.write_artifact) with one record per (ratio,
mode).  Sub-capacity points additionally carry ``us_per_query`` (p50
request latency) so benchmarks/check_regress.py gates them as rolling-
median series — overloaded points are queue-dominated by design and stay
out of the regression gate.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.common import emit, record, write_artifact
from repro.compile_cache import enable_compile_cache

# the sweep: offered QPS as multiples of measured capacity; >= SHED_BOUND
# ratios are past the knee, where the two modes must diverge
RATIOS = (0.5, 1.0, 2.0, 5.0)
# only the sub-capacity point feeds the regression-gate history: at >= 1x
# capacity the latency is queue-dominated and a critically-loaded queue's
# wait is inherently high-variance run to run
GATED_RATIOS = (0.5,)
TRACE_SAMPLE = 0.25


def _requests(cfg, n: int, *, tokens: int, deadline: float | None, seed: int):
    from repro.serve.engine import Request

    g = np.random.default_rng(seed)
    return [
        Request(
            rid=i, prompt=g.integers(0, cfg.vocab_size, 6).astype(np.int32),
            max_new_tokens=tokens, deadline_s=deadline,
        )
        for i in range(n)
    ]


def _arrivals(n: int, qps: float, seed: int) -> np.ndarray:
    """Poisson-process arrival offsets (seconds from sweep start)."""
    g = np.random.default_rng(seed)
    return np.cumsum(g.exponential(1.0 / qps, size=n))


def drive(engine, reqs, arrivals) -> list:
    """Open-loop driver: submit each request at its scheduled arrival time,
    interleaved with ``engine.step()`` service; arrivals never wait for
    completions.  Returns every terminal request (completed + shed,
    including submit-time rejections, which run()/step() do not return)."""
    finished = []
    t0 = time.perf_counter()
    i = 0
    while i < len(reqs) or engine.busy:
        now = time.perf_counter() - t0
        while i < len(reqs) and arrivals[i] <= now:
            r = reqs[i]
            i += 1
            if not engine.submit(r):
                finished.append(r)  # rejected on submit
        if engine.busy:
            finished.extend(engine.step())
        elif i < len(reqs):
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.01))
    return finished


def _point(engine, cfg, *, n, qps, tokens, deadline, seed, events):
    """One operating point on a fresh registry; returns the metrics the
    sweep records."""
    from repro.obs import EventLog, Registry
    from repro.serve.engine import (
        SHED_EARLY,
        SHED_EXPIRED_FLIGHT,
        SHED_EXPIRED_QUEUE,
        SHED_REJECTED,
    )

    reg = engine.reset_metrics(
        Registry(events=EventLog(events)) if events else None
    )
    reqs = _requests(cfg, n, tokens=tokens, deadline=deadline, seed=seed)
    finished = drive(engine, reqs, _arrivals(n, qps, seed + 1))
    assert len(finished) == n, "driver lost a request"

    snap = reg.snapshot()
    lat = snap["histograms"].get("serve.request_latency_s", {})
    shed = {
        r: reg.value("serve.shed", reason=r)
        for r in (SHED_REJECTED, SHED_EXPIRED_QUEUE, SHED_EXPIRED_FLIGHT,
                  SHED_EARLY)
    }
    submitted = reg.value("serve.submitted")
    completed = reg.value("serve.completed")
    steps = reg.value("serve.steps")
    occupancy = (
        reg.value("serve.tokens") / (steps * engine.num_slots) if steps else 0.0
    )
    assert submitted == n
    assert completed + sum(shed.values()) == n, "shed accounting leak"
    return {
        "n": n,
        "completed": completed,
        "shed": shed,
        "shed_rate": sum(shed.values()) / n,
        "p50_s": lat.get("p50", float("nan")),
        "p95_s": lat.get("p95", float("nan")),
        "p99_s": lat.get("p99", float("nan")),
        "queue_wait_p99_s": snap["histograms"]
        .get("serve.queue_wait", {})
        .get("p99", 0.0),
        "occupancy": occupancy,
        "steps": steps,
        "finished": finished,
    }


def _slowest_sampled_trace(finished, events: str) -> str | None:
    """Reconstruct + render the slowest completed trace-sampled request's
    span tree (the PR 8 path): the bench's tail-latency explanation."""
    from repro.obs import Trace

    done = [r for r in finished if getattr(r, "done", False) and r.trace]
    done = [r for r in done if r.trace.sampled]
    if not done:
        return None
    worst = max(done, key=lambda r: r.latency_s)
    try:
        return Trace.reconstruct(events, worst.trace.trace_id).render()
    except (KeyError, ValueError, OSError):  # sampled but log rotated/unset
        return None


def run(smoke: bool = False, events: str | None = None) -> int:
    import jax

    from repro.configs import get_smoke_config
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    # n_per_point must be large enough that the backlog a >=2x overload
    # builds up (~ n/capacity * (1 - 1/ratio) of queue wait by the last
    # arrival) comfortably exceeds the deadline — otherwise the whole burst
    # drains inside every budget and the knee never shows
    if smoke:
        n_per_point, tokens, num_slots, max_len = 150, 8, 4, 32
    else:
        n_per_point, tokens, num_slots, max_len = 500, 16, 8, 64

    cfg = get_smoke_config("smollm-135m")
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(
        model, params, num_slots=num_slots, max_len=max_len,
        trace_sample=TRACE_SAMPLE if events else 0.0,
    )

    # warm twice: the first run pays prefill+decode compilation; the second
    # measures the steady state the capacity estimate and deadline hang off.
    # Capacity is MEASURED closed-loop throughput (requests / wall), which
    # prices everything the engine pays per request — refill, decode steps,
    # scheduler overhead — not just the decode-step arithmetic.
    service_p50 = capacity_qps = 0.0
    for w in range(2):
        engine.reset_metrics()
        for r in _requests(cfg, 4 * num_slots, tokens=tokens, deadline=None,
                           seed=90 + w):
            engine.submit(r)
        t0 = time.perf_counter()
        engine.run()
        capacity_qps = 4 * num_slots / (time.perf_counter() - t0)
        service_p50 = engine.metrics()["histograms"][
            "serve.request_latency_s"]["p50"]

    step_s = engine.step_time_s()
    # budget: a few end-to-end service times — comfortably met below the
    # knee, decisively violated by unbounded queueing above it
    deadline = 3.0 * service_p50
    bound = deadline + 3.0 * service_p50  # admitted-p99 ceiling (gate)
    print(f"# capacity ~{capacity_qps:.1f} qps (step {step_s*1e3:.2f} ms, "
          f"service p50 {service_p50*1e3:.1f} ms); deadline {deadline*1e3:.1f} ms")

    results: dict[tuple[float, str], dict] = {}
    for ratio in RATIOS:
        qps = ratio * capacity_qps
        for mode, dl in (("noshed", None), ("shed", deadline)):
            m = _point(
                engine, cfg, n=n_per_point, qps=qps, tokens=tokens,
                deadline=dl, seed=int(ratio * 100), events=events,
            )
            results[(ratio, mode)] = m
            emit(f"serve/{mode}_q{ratio:g}x", m["p50_s"] * 1e6,
                 f"qps={qps:.1f};p99_ms={m['p99_s']*1e3:.1f};"
                 f"shed_rate={m['shed_rate']:.2f};occ={m['occupancy']:.2f}")
            rec = dict(
                dataset="ServeSmoke" if smoke else "Serve",
                method=f"{mode}-q{ratio:g}x",
                offered_qps=qps, ratio=ratio, mode=mode,
                deadline_s=dl, n=m["n"], completed=m["completed"],
                shed_rejected=m["shed"]["rejected"],
                shed_expired_queue=m["shed"]["expired_queue"],
                shed_expired_flight=m["shed"]["expired_flight"],
                shed_early=m["shed"]["early"],
                shed_rate=m["shed_rate"],
                p50_s=m["p50_s"], p95_s=m["p95_s"], p99_s=m["p99_s"],
                queue_wait_p99_s=m["queue_wait_p99_s"],
                slot_occupancy=m["occupancy"], steps=m["steps"],
            )
            if ratio in GATED_RATIOS:
                # regression-gate series: stable (not queue-dominated) points
                rec["us_per_query"] = m["p50_s"] * 1e6
            record("serve", f"{mode}_q{ratio:g}x", **rec)

    # tail-latency explanation via the trace path (heaviest shed point)
    if events:
        tree = _slowest_sampled_trace(
            results[(RATIOS[-1], "shed")]["finished"], events
        )
        if tree:
            print("# slowest sampled admitted request at "
                  f"{RATIOS[-1]:g}x (shed mode):")
            for line in tree.splitlines():
                print(f"#   {line}")

    write_artifact("serve", meta=dict(
        smoke=smoke, n_per_point=n_per_point, tokens=tokens,
        num_slots=num_slots, capacity_qps=capacity_qps,
        decode_step_s=step_s, service_p50_s=service_p50,
        deadline_s=deadline, p99_bound_s=bound,
    ))

    # --- hard gates --------------------------------------------------------
    failures = []
    for ratio in RATIOS:
        p99 = results[(ratio, "shed")]["p99_s"]
        if not (np.isnan(p99) or p99 <= bound):
            failures.append(
                f"shed p99 unbounded at {ratio:g}x: {p99*1e3:.1f} ms "
                f"> bound {bound*1e3:.1f} ms"
            )
    top = RATIOS[-1]
    m_shed, m_raw = results[(top, "shed")], results[(top, "noshed")]
    if m_shed["shed_rate"] <= 0.0:
        failures.append(f"no shedding at {top:g}x capacity — knee not reached")
    if not m_raw["p99_s"] > m_shed["p99_s"]:
        failures.append(
            f"no-shedding p99 ({m_raw['p99_s']*1e3:.1f} ms) does not exceed "
            f"shedding p99 ({m_shed['p99_s']*1e3:.1f} ms) at {top:g}x"
        )
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(f"serve bench OK: {len(RATIOS)} qps points x 2 modes; at {top:g}x "
          f"capacity shed p99 {m_shed['p99_s']*1e3:.1f} ms (bounded) vs "
          f"noshed {m_raw['p99_s']*1e3:.1f} ms, "
          f"shed rate {m_shed['shed_rate']:.0%}")
    return 0


if __name__ == "__main__":
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--events", default=os.environ.get("REPRO_OBS_EVENTS"),
                    help="JSONL span log for trace-sampled requests "
                    "(default: $REPRO_OBS_EVENTS)")
    a = ap.parse_args()
    enable_compile_cache()
    raise SystemExit(run(smoke=a.smoke, events=a.events or None))
