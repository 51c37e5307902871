"""Production serving front: continuous batching, per-request deadlines
with admission control + load shedding, and query/ingest fairness — with
kNN-LM retrieval (the paper's datastore) fused into every decode step.

The traffic model (see serve/README.md for the full lifecycle):

* **continuous batching** — a fixed decode batch of ``num_slots`` (one
  idle lane more for a single slot, see ``lanes``);
  finished/expired/empty slots are refilled from the request queue between
  steps.  The jitted decode step never recompiles because shapes are
  static, and per-slot cache positions make mid-flight refill *safe*: one
  step advances every slot at ITS own position (position-masked attention;
  see layers.decode_attention), so a freshly admitted request decodes from
  its own prompt length while its neighbors are deep into generation;
* **deadlines + load shedding** — ``Request.deadline_s`` is a latency
  budget relative to submit.  Admission control rejects at ``submit()``
  when the *projected* queue wait (measured decode-step time x backlog
  work / slots) already exceeds the budget; queued requests whose budget
  expires are shed before they waste a prefill; a mid-flight request whose
  budget expires is evicted from its slot before the next step.  Every
  shed is terminal (``req.shed``/``req.shed_reason``) and counted under
  ``serve.shed{reason=...}``, and the conservation invariant
  ``submitted == completed + shed + in_flight`` holds at every step
  boundary (tests/test_serve_front.py pins it);
* **query/ingest fairness** — mixed read+write traffic shares the engine;
  ``_drain_ingest`` applies at most ``max_ingest_per_step`` ingest batches
  between decode steps, so a sustained ingest stream can no longer starve
  queued queries (each deferral increments ``serve.ingest_deferred``);
* **retrieval** — the datastore is an ARGUMENT of the jitted decode step
  (not a closure capture): delta shapes are fixed at build, so ingest
  swaps buffer contents without a single recompile;
* **telemetry** (repro.obs): request/ingest latency histograms with
  serving percentiles, queue-depth / slot-occupancy gauges, shed and
  fairness counters, prefill/decode-step span timings —
  ``engine.metrics()`` snapshots them all, and sampled requests emit a
  linked span tree (queue wait -> prefill -> completion root) for
  ``Trace.reconstruct``.

``run()`` drives the queues to completion (offline / test harness);
``step()`` is one scheduler iteration, exposed so an open-loop driver
(benchmarks/bench_serve.py) can interleave arrivals with service exactly
as a network front would.

Single-host implementation of the multi-host pattern: on a real mesh the
same engine runs with params/caches sharded exactly as in the dry-run.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs import Registry, TraceContext, TraceSampler, use_trace
from repro.serve.retrieval import Datastore, ForestDatastore, ingest_keys

PyTree = Any

# shed reasons (Request.shed_reason / serve.shed{reason=...} counter labels)
SHED_REJECTED = "rejected"  # admission control refused at submit()
SHED_EXPIRED_QUEUE = "expired_queue"  # deadline passed while waiting in queue
SHED_EXPIRED_FLIGHT = "expired_flight"  # deadline passed while decoding
# speculative early expiry: the deadline has NOT lapsed yet, but the tokens
# still owed x the measured step time already overrun it — shedding now
# returns the slot instead of burning doomed decode steps until the clock
# catches up
SHED_EARLY = "early"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int = 16
    # latency budget in seconds, relative to submit(); None = no deadline
    # (never rejected, never expired — the pre-deadline behavior)
    deadline_s: float | None = None
    out_tokens: list[int] = field(default_factory=list)
    done: bool = False  # completed normally (terminal)
    shed: bool = False  # load-shed (terminal; never set together with done)
    shed_reason: str = ""  # one of the SHED_* constants when shed
    # submit -> terminal state, queue wait INCLUDED (completed OR shed) —
    # the latency a client sees, and what the deadline budgets against
    latency_s: float = 0.0
    # tracing: assigned at submit() by the engine's sampler (or preset by
    # the caller); sampled requests emit a linked span tree — queue wait,
    # prefill, and a "serve.request_latency_s" root — into the registry's
    # event log
    trace: TraceContext | None = None
    _t0: float = 0.0  # perf_counter at slot admission (queue-wait accounting)
    _t_submit: float = 0.0  # perf_counter at submit (queue-wait accounting)
    _t_deadline: float = 0.0  # absolute perf_counter deadline (0 = none)

    @property
    def state(self) -> str:
        """Terminal: ``"done"`` / ``"shed"``; live: ``"running"`` (owns a
        slot) / ``"queued"`` (submitted) / ``"new"`` (never submitted)."""
        if self.shed:
            return "shed"
        if self.done:
            return "done"
        if self._t0 > 0.0:
            return "running"
        return "queued" if self._t_submit > 0.0 else "new"


@dataclass
class IngestRequest:
    """Insert (key, next-token) pairs into the serving datastore's delta.

    Requires a ForestDatastore built with ``stream_capacity > 0``.
    ``accepted`` reports how many pairs fit the destination buffers (the
    rest were capacity-rejected; clients re-submit after maintenance)."""

    rid: int
    keys: np.ndarray  # (B, Dk) f32
    values: np.ndarray  # (B,) i32 token ids
    accepted: int = 0
    done: bool = False
    latency_s: float = 0.0
    error: str = ""  # non-empty when the engine could not ingest at all


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params: PyTree,
        *,
        num_slots: int = 4,
        max_len: int = 256,
        datastore: Datastore | None = None,
        greedy: bool = True,
        registry: Registry | None = None,
        trace_sample: float = 0.0,
        max_ingest_per_step: int = 8,
        step_time_hint_s: float | None = None,
    ):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.datastore = datastore
        self.greedy = greedy
        # decode lanes: a batch of ONE row takes XLA's vector-times-matrix
        # path, whose sums round differently from the matrix path any
        # larger batch takes (on TPU v5e and on CPU), so a 1-slot engine
        # keeps a spare idle lane and a request's tokens do not depend on
        # the slot count
        self.lanes = max(num_slots, 2)
        self.cache = model.init_cache(self.lanes, max_len)
        self.slot_req: list[Request | None] = [None] * num_slots
        self.slot_pos = np.zeros(self.lanes, np.int32)
        self.queue: list[Request] = []
        self.ingest_queue: list[IngestRequest] = []
        self._decode = jax.jit(self._decode_step)
        # slot refill is jitted end to end (prefill + cache merge + first
        # token): eagerly it costs ~1000 decode steps of per-op dispatch on
        # CPU, which would make admission — not decode — the bottleneck.
        # Re-traces once per distinct prompt LENGTH (shapes are static);
        # fronts with wildly variable prompts should pad to a few buckets.
        self._prefill = jax.jit(self._prefill_merge)
        self.steps = 0
        # query/ingest fairness: at most this many ingest batches apply per
        # scheduler step, so a saturating write stream cannot starve reads
        if max_ingest_per_step < 1:
            raise ValueError(
                f"max_ingest_per_step={max_ingest_per_step} must be >= 1 "
                "(ingest batches applied between decode steps)"
            )
        self.max_ingest_per_step = max_ingest_per_step
        # admission control's service-time model: median of recent decode
        # step wall times (a median shrugs off the compile-heavy first
        # step, which an EWMA would drag around for dozens of steps).
        # ``step_time_hint_s`` seeds it for deterministic admission before
        # the first measured step (tests; cold engines admit everything).
        self._step_times: deque[float] = deque(maxlen=32)
        if step_time_hint_s is not None:
            self._step_times.append(float(step_time_hint_s))
        # serving telemetry (repro.obs): request/ingest latency percentile
        # histograms + queue-depth / slot-occupancy gauges replace the old
        # scatter of per-request perf_counter fields as the ENGINE's view
        # (requests keep their latency_s for per-request callers)
        self.obs = registry if registry is not None else Registry()
        # per-request tracing: ``trace_sample`` of submitted decode requests
        # get a TraceContext (deterministic systematic sampling); their
        # queue-wait/prefill spans and completion root land in the
        # registry's event log for Trace.reconstruct
        self._tracer = TraceSampler(trace_sample)

    def metrics(self) -> dict[str, Any]:
        """One snapshot of the engine's registry: ``serve.*`` latency
        histograms (seconds, p50/p95/p99), queue/slot gauges, shed and
        fairness counters, and step/token counters."""
        return self.obs.snapshot()

    def reset_metrics(self, registry: Registry | None = None) -> Registry:
        """Swap the engine onto a fresh (or provided) registry and return
        it.  The service-time model and compiled programs persist — this
        exists so a sweep (benchmarks/bench_serve.py) can isolate each
        operating point's percentiles without rebuilding the engine."""
        self.obs = registry if registry is not None else Registry()
        return self.obs

    @property
    def busy(self) -> bool:
        """True while any work remains (live slots, queued decodes, or a
        pending ingest backlog)."""
        return (
            any(r is not None for r in self.slot_req)
            or bool(self.queue)
            or bool(self.ingest_queue)
        )

    # --- admission control --------------------------------------------------
    def step_time_s(self) -> float | None:
        """Current decode-step service-time estimate (median of recent
        measured steps), or None before any step ran."""
        if not self._step_times:
            return None
        return float(np.median(self._step_times))

    def projected_wait_s(self) -> float:
        """Projected queue wait for a request submitted NOW: the backlog's
        remaining decode work (tokens still owed to live slots + every
        queued request's full budget) drained through ``num_slots`` servers
        at the measured step time.  FCFS: a new request starts once that
        backlog has dispatched.  0.0 on a cold engine (no estimate yet —
        admit and let measurements accumulate).  Prefill cost is
        deliberately excluded: it is one step-shaped unknown per request
        and the projection only needs to be honest about the *queue*, which
        decode steps dominate."""
        step_s = self.step_time_s()
        if step_s is None:
            return 0.0
        inflight = sum(
            max(r.max_new_tokens - len(r.out_tokens), 0)
            for r in self.slot_req if r is not None
        )
        queued = sum(r.max_new_tokens for r in self.queue)
        return step_s * (inflight + queued) / self.num_slots

    def _shed(self, req: Request, reason: str, now: float) -> None:
        """Terminal shed: mark, count, observe the wasted wait, and — for a
        sampled request — close its trace tree with a shed root."""
        req.shed = True
        req.shed_reason = reason
        req.latency_s = now - req._t_submit if req._t_submit else 0.0
        self.obs.counter("serve.shed", reason=reason).inc()
        # observes serve.shed_wait_s AND (sampled + event log) emits the
        # trace root, so a shed request's tree closes like a completed one's
        self.obs.emit_trace_root(req.trace, "serve.shed_wait_s", req.latency_s)

    # --- jitted single step over all slots -------------------------------
    # ``datastore`` is a traced argument: ingest swaps in new delta contents
    # between steps and the same compiled step sees them (shapes are static).
    def _decode_step(self, params, tokens, cache, pos, datastore):
        logits, cache = self.model.decode_step(
            params, tokens, cache, pos, datastore=datastore
        )
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache

    # --- jitted slot refill: prefill + merge into the slot's cache lane ----
    # ``slot`` is a traced scalar, so one compiled program serves every slot.
    def _prefill_merge(self, params, prompt, cache, slot):
        logits, cache1 = self.model.prefill(
            params, {"tokens": prompt}, max_len=self.max_len
        )
        merged = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=self._batch_axis(full)
            ),
            cache, cache1,
        )
        return jnp.argmax(logits[0, -1]).astype(jnp.int32), merged

    # --- slot management ---------------------------------------------------
    def submit(self, req: Request | IngestRequest) -> bool:
        """Enqueue a request.  Returns False when admission control shed a
        decode request on the spot (``req.shed``/``req.shed_reason`` are
        set; the request never enters the queue and will NOT be returned by
        ``run()``/``step()`` — the submitter already holds it)."""
        if isinstance(req, IngestRequest):
            self.ingest_queue.append(req)
            return True
        now = time.perf_counter()
        req._t_submit = now
        self.obs.counter("serve.submitted").inc()
        if req.deadline_s is not None:
            req._t_deadline = now + req.deadline_s
            projected = self.projected_wait_s()
            self.obs.gauge("serve.projected_wait_s").set(projected)
            if projected > req.deadline_s:
                # reject-on-submit: the queue already owes more work than
                # this budget covers — shedding NOW costs nothing, admitting
                # would waste a prefill + queue slot on a doomed request
                self._shed(req, SHED_REJECTED, now)
                return False
        if req.trace is None:
            req.trace = self._tracer.maybe_trace()
        self.queue.append(req)
        return True

    def _drain_ingest(self) -> list[IngestRequest]:
        """Apply queued inserts to the datastore (between decode steps).

        Bounded: at most ``max_ingest_per_step`` batches per call, so a
        sustained ingest stream yields the engine back to queued queries
        every step (the deferred remainder is counted once per bounded
        stop under ``serve.ingest_deferred``)."""
        done: list[IngestRequest] = []
        streamable = (
            isinstance(self.datastore, ForestDatastore)
            and self.datastore.delta is not None
        )
        budget = self.max_ingest_per_step
        while self.ingest_queue and budget > 0:
            budget -= 1
            req = self.ingest_queue.pop(0)
            t0 = time.perf_counter()
            if not streamable:
                # fail THIS request, not the whole run loop (in-flight
                # decode requests must survive a misdirected insert)
                req.accepted = 0
                req.error = "datastore does not accept streaming inserts"
                self.obs.counter("serve.ingest_errors").inc()
            else:
                with self.obs.span("serve.ingest"):
                    self.datastore, n_acc = ingest_keys(
                        self.datastore, jnp.asarray(req.keys, jnp.float32),
                        jnp.asarray(req.values, jnp.int32),
                    )
                req.accepted = n_acc
                self.obs.counter("serve.ingested_keys").inc(n_acc)
            req.done = True
            req.latency_s = time.perf_counter() - t0
            self.obs.histogram("serve.ingest_latency_s").observe(req.latency_s)
            done.append(req)
        if self.ingest_queue:
            # fairness observable: the bound bit — queries get the next step
            self.obs.counter("serve.ingest_deferred").inc()
        return done

    def _expire_queue(self) -> list[Request]:
        """Shed queued requests whose deadline passed before they reached a
        slot — cheaper than admitting them into a doomed prefill."""
        now = time.perf_counter()
        expired = [
            r for r in self.queue if r._t_deadline and now > r._t_deadline
        ]
        if expired:
            self.queue = [
                r for r in self.queue
                if not (r._t_deadline and now > r._t_deadline)
            ]
            for r in expired:
                self._shed(r, SHED_EXPIRED_QUEUE, now)
        return expired

    def _expire_slots(self) -> list[Request]:
        """Evict mid-flight requests whose deadline passed — and,
        speculatively, those that cannot possibly finish in time: once the
        tokens still owed times the measured step time overrun the budget,
        the request is doomed, so shedding it NOW (reason ``"early"``)
        frees the slot for the refill below instead of burning steps until
        the clock catches up.  Partial ``out_tokens`` stay on the request
        either way (a caller may still use a truncated answer)."""
        now = time.perf_counter()
        step_s = self.step_time_s()
        evicted: list[Request] = []
        for s in range(self.num_slots):
            req = self.slot_req[s]
            if req is None or not req._t_deadline:
                continue
            if now > req._t_deadline:
                self._shed(req, SHED_EXPIRED_FLIGHT, now)
            elif step_s is not None:
                # tokens this slot still owes: budget remainder, capped by
                # the cache-length retirement below (slot_pos >= max_len-1)
                remaining = min(
                    req.max_new_tokens - len(req.out_tokens),
                    self.max_len - 1 - int(self.slot_pos[s]),
                )
                if now + remaining * step_s <= req._t_deadline:
                    continue
                self._shed(req, SHED_EARLY, now)
            else:
                continue
            self.slot_req[s] = None
            self.slot_pos[s] = 0
            evicted.append(req)
        return evicted

    def _fill_slots(self) -> None:
        for slot in range(self.num_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            req._t0 = time.perf_counter()
            prompt = jnp.asarray(req.prompt[None, :], jnp.int32)
            with use_trace(req.trace):
                # queue wait was measured outside any span — record it into
                # the request's tree with the externally-measured duration
                self.obs.record_span(
                    "serve.queue_wait", req._t0 - req._t_submit
                )
                with self.obs.span("serve.prefill"):
                    first, self.cache = self._prefill(
                        self.params, prompt, self.cache, slot
                    )
                    first = int(first)  # block: the refill's real wall time
            req.out_tokens.append(first)
            self.slot_req[slot] = req
            self.slot_pos[slot] = len(req.prompt)

    def _batch_axis(self, leaf) -> int:
        # stage caches are stacked (n, B, ...) when scanned; (B, ...) when not
        return 1 if leaf.ndim >= 2 and leaf.shape[1] == self.lanes else 0

    # --- scheduler ----------------------------------------------------------
    def step(self) -> list[Request | IngestRequest]:
        """One scheduler iteration: bounded ingest drain -> queue/slot
        deadline expiry -> slot refill (continuous batching) -> one batched
        decode step -> retire.  Returns every request that reached a
        terminal state during the iteration (completed decodes, shed
        decodes, ingest acks) — the unit an open-loop driver interleaves
        with arrivals."""
        finished: list[Request | IngestRequest] = []
        finished.extend(self._drain_ingest())
        finished.extend(self._expire_queue())
        finished.extend(self._expire_slots())
        self._fill_slots()
        live = [s for s in range(self.num_slots) if self.slot_req[s] is not None]
        self.obs.gauge("serve.queue_depth").set(len(self.queue))
        self.obs.gauge("serve.ingest_queue_depth").set(len(self.ingest_queue))
        self.obs.gauge("serve.slot_occupancy").set(len(live) / self.num_slots)
        if not live:
            return finished
        # per-slot positions: a freshly refilled slot with a shorter
        # prompt keeps decoding at ITS cache position — stepping every
        # slot at max(live positions) would skip past the refilled
        # slot's prompt and corrupt its decode.  Empty slots step at
        # their stale position and decode garbage, ignored.
        tokens = np.zeros((self.lanes, 1), np.int32)
        for s in live:
            tokens[s, 0] = self.slot_req[s].out_tokens[-1]
        t_step = time.perf_counter()
        with self.obs.span("serve.decode_step"):
            nxt, self.cache = self._decode(
                self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(self.slot_pos), self.datastore,
            )
            nxt = np.asarray(nxt)  # block: the step's real wall time
        self._step_times.append(time.perf_counter() - t_step)
        self.steps += 1
        self.obs.counter("serve.steps").inc()
        self.obs.counter("serve.tokens").inc(len(live))
        for s in live:
            req = self.slot_req[s]
            req.out_tokens.append(int(nxt[s]))
            self.slot_pos[s] += 1
            if len(req.out_tokens) >= req.max_new_tokens \
                    or self.slot_pos[s] >= self.max_len - 1:
                req.done = True
                self.obs.counter("serve.completed").inc()
                req.latency_s = time.perf_counter() - req._t_submit
                # observes serve.request_latency_s AND — for a sampled
                # request with an event log attached — emits the trace's
                # root span, closing the tree the queue-wait/prefill
                # spans already parented to
                self.obs.emit_trace_root(
                    req.trace, "serve.request_latency_s", req.latency_s
                )
                finished.append(req)
                self.slot_req[s] = None
                self.slot_pos[s] = 0
        return finished

    def run(self, *, max_steps: int = 10_000) -> list[Request | IngestRequest]:
        """Process the queues to completion; returns finished requests
        (completed decodes, shed decodes, ingest acks, in completion
        order).  ``max_steps`` bounds DECODE steps; a pure ingest backlog
        always drains (each call applies up to ``max_ingest_per_step``)."""
        finished: list[Request | IngestRequest] = []
        while self.busy and self.steps < max_steps:
            finished.extend(self.step())
        return finished
