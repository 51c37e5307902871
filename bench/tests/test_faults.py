"""A run with the timed path broken underneath reads ``correct`` false.

The run skips the look for a chip and is otherwise whole: data, build,
warm-up, window, comparison.  The faults a read-only search cell can have:
an answer altered where the executor produces it, and half of the batch
left out (its answers copied from the other half).  The cells hold no state
that a step could leave unchanged and run on one chip, so those two faults
do not arise."""
import time

import jax.numpy as jnp
import pytest

import harness
from repro.api.index import OverlapIndex
from small import small_cell

PLANNED = OverlapIndex._search_planned


def altered(self, q, **kw):
    d, i, *rest = PLANNED(self, q, **kw)
    return (d, i.at[:, 0].set((i[:, 0] + 1) % self.n_total), *rest)


def half_batch(self, q, **kw):
    h = len(q) // 2
    d, i, *rest = PLANNED(self, q[:h], **kw)
    return (jnp.concatenate([d, d]), jnp.concatenate([i, i]), *rest)


def run(workload, trace=False):
    return harness.run_cell(small_cell(workload), 2**31 + 11, 1.0, trace, time.perf_counter(),
                            require_chip=False)


@pytest.mark.parametrize("workload", ["db1-exact-k10", "db1-exact-k100"])
def test_sound_run_is_correct(workload):
    got = run(workload)
    assert got["correct"] and got["failed"] == 0, got["checks"]
    assert set(got["metrics"]) == {"query_throughput", "latency_p90_ms", "setup_s"}


def test_traced_run_reads_per_layer_metrics():
    got = run("db1-exact-k10", trace=True)
    assert got["correct"]
    # the CPU has no device trace: only the program's own spans and counters
    assert {"facade.plan_lookup_us", "planner.compiles_in_window",
            "executor.scan_steps_per_batch", "executor.buckets_per_query"} <= set(got["metrics"])
    assert got["metrics"]["planner.compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", [altered, half_batch], ids=["altered-answer", "half-batch"])
@pytest.mark.parametrize("workload", ["db1-exact-k10", "db1-exact-k100", "ward-exact-k10"])
def test_fault_reads_incorrect(workload, fault, monkeypatch):
    monkeypatch.setattr(OverlapIndex, "_search_planned", fault)
    got = run(workload)
    assert not got["correct"] and got["failed"] > 0, got["checks"]
