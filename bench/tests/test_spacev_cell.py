"""The SPACEV cell, cut to a CPU test's size: its stand-in data has SPACEV's
published form (int8, 100-d), the int8 store holds it exactly, a sound run
reads ``correct`` true, an altered answer false, the control (the cross
term in three bf16 passes) fails the limits, and so does the lossy
per-member-scale int8 format forced in."""
import time

import numpy as np
import pytest

import calibrate
import harness
import lookup
from repro.api.index import OverlapIndex
from repro.kernels import ops as kops
from small import small_cell

from test_faults import altered

CELL = "spacev-int8-k10"


def run(seed=2**31 + 13):
    return harness.run_cell(small_cell(CELL), seed, 1.0, False, time.perf_counter(),
                            require_chip=False)


def test_generator_gives_int8_embeddings():
    x = lookup.generator("spacev_like")(20_000, 100, 2)
    assert x.shape == (20_000, 100) and x.dtype == np.float32
    assert np.array_equal(x, np.rint(x)) and x.min() >= -128 and x.max() <= 127
    # rows rarely reach the int8 bounds: a per-member scale is lossy on them
    at_bound = ((x == -128) | (x == 127)).any(1)
    assert at_bound.mean() < 1e-3
    assert np.array_equal(x, lookup.generator("spacev_like")(20_000, 100, 2))


def test_store_is_exact_int8():
    cell = small_cell(CELL)
    x, _, ix = harness.prepare(cell["config"], cell["traffic"], 2**31 + 5)
    dev = ix.device
    assert dev.bucket_x.dtype == np.int8 and dev.bucket_offset is not None
    live = np.asarray(dev.bucket_ids) >= 0
    m = kops.Int8Members(
        np.asarray(dev.bucket_x)[live], np.asarray(dev.bucket_scale)[live],
        dev.bucket_offset,
    )
    np.testing.assert_array_equal(np.asarray(kops.dequantize_members(m)),
                                  x[np.asarray(dev.bucket_ids)[live]])
    m = ix.metrics()["build"]
    assert m["member_format"] == 1
    assert m["member_bytes"] == dev.bucket_x.size + 4 * dev.bucket_scale.size + 4 * 100


def test_sound_run_is_correct():
    got = run()
    assert got["correct"] and got["failed"] == 0, got["checks"]
    assert set(got["metrics"]) == {"query_throughput", "latency_p90_ms", "setup_s"}


def test_altered_answer_reads_incorrect(monkeypatch):
    monkeypatch.setattr(OverlapIndex, "_search_planned", altered)
    got = run()
    assert not got["correct"] and got["failed"] > 0, got["checks"]


def test_per_member_scale_reads_incorrect(monkeypatch):
    """The int8 format before the exact one: a symmetric per-member scale,
    lossy on byte data whose rows stop short of +-127."""

    def scaled(x, valid=None):
        return kops.Int8Members(*kops.quantize_datastore(x), None)

    monkeypatch.setattr(kops, "quantize_members", scaled)
    got = run()
    assert not got["correct"] and got["failed"] > 0, got["checks"]
    assert got["checks"]["answer_err"]["value"] > 100 * got["checks"]["answer_err"]["limit"]


@pytest.mark.parametrize("seed", [3, 40_000_000_019])
def test_control_fails_where_the_program_passes(seed):
    cell = small_cell(CELL)
    limits = cell["config"]["limits"]
    got = calibrate.readings(cell, seed, seconds=0.5)
    assert all(got["program"][n] <= lim for n, lim in limits.items()), got["program"]
    assert any(got["control"][n] > lim for n, lim in limits.items()), got["control"]
