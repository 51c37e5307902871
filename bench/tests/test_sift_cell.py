"""The SIFT cell, cut to a CPU test's size: its stand-in data has SIFT's
form, a sound run reads ``correct`` true, an altered answer false, and the
control (the cross term in three bf16 passes) fails the limits."""
import time

import numpy as np
import pytest

import calibrate
import harness
import lookup
from repro.api.index import OverlapIndex
from small import small_cell

from test_faults import altered

CELL = "sift-exact-k10"


def run(seed=2**31 + 13):
    return harness.run_cell(small_cell(CELL), seed, 1.0, False, time.perf_counter(),
                            require_chip=False)


def test_generator_gives_sift_descriptors():
    x = lookup.generator("sift_like")(4000, 128, 2)
    assert x.shape == (4000, 128) and x.dtype == np.float32
    assert np.array_equal(x, np.rint(x)) and x.min() >= 0 and x.max() <= 255
    norms = np.linalg.norm(x, axis=1)
    # normalised to 512, then rounded: each of 128 bins moves by at most 0.5
    assert np.abs(norms - 512.0).max() < 4.0
    # Lowe's clip at 0.2 of the unit descriptor holds up to the renormalisation
    assert (x / norms[:, None]).max() < 0.3
    assert np.array_equal(x, lookup.generator("sift_like")(4000, 128, 2))


def test_sound_run_is_correct():
    got = run()
    assert got["correct"] and got["failed"] == 0, got["checks"]
    assert set(got["metrics"]) == {"query_throughput", "latency_p90_ms", "setup_s"}


def test_altered_answer_reads_incorrect(monkeypatch):
    monkeypatch.setattr(OverlapIndex, "_search_planned", altered)
    got = run()
    assert not got["correct"] and got["failed"] > 0, got["checks"]


@pytest.mark.parametrize("seed", [3, 40_000_000_019])
def test_control_fails_where_the_program_passes(seed):
    cell = small_cell(CELL)
    limits = cell["config"]["limits"]
    got = calibrate.readings(cell, seed, seconds=0.5)
    assert all(got["program"][n] <= lim for n, lim in limits.items()), got["program"]
    assert any(got["control"][n] > lim for n, lim in limits.items()), got["control"]
