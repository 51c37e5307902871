"""The trace reduction and the roofline count, on a small recorded trace
and on events whose answers are known."""
import json
from pathlib import Path

import numpy as np
import pytest

import devtrace
import roofline

DATA = Path(__file__).resolve().parent / "data"
V5E = json.loads((Path(devtrace.__file__).parent / "peaks.json").read_text())["devices"]["TPU v5 lite"]


@pytest.fixture(scope="module")
def recorded():
    """One k=10 search batch of db1-tracking traced on a TPU v5e."""
    return json.loads((DATA / "trace_one_batch.json").read_text())


def test_recorded_trace_window_busy_and_idle(recorded):
    s = devtrace.summarize(recorded)
    window = recorded["host"][-1][2] / 1e9
    assert s.window_s == pytest.approx(window)
    ops = [(a, a + d) for _, a, d in recorded["devices"]["/device:TPU:0"]]
    busy = sum(b - a for a, b in devtrace.union(ops)) / 1e9
    assert s.busy_s == pytest.approx(busy)
    assert 0.0 < s.idle_share < 1.0
    assert sum(s.gaps_s.values()) == pytest.approx(window - busy)


def test_recorded_trace_kernel_time(recorded):
    s = devtrace.summarize(recorded)
    calls = [d for n, _, d in recorded["devices"]["/device:TPU:0"]
             if n.startswith("%bucket_scan_topk_pallas.")]
    assert calls, "the recorded trace holds the bucket-scan kernel"
    assert s.op_seconds("bucket_scan_topk_pallas") == pytest.approx(sum(calls) / 1e9)
    assert s.op_seconds("bucket_scan_topk_pallas") <= s.busy_s
    # self times never add up to more than the time the device was busy
    assert sum(s.op_s.values()) <= s.busy_s * (1 + 1e-9)
    top = s.breakdown()
    assert top["device_ops"][0][0] == "bucket_scan_topk_pallas"
    assert len(top["device_ops"]) <= devtrace.TOP and len(top["idle_gaps"]) <= devtrace.TOP


def test_known_events():
    ms = 1_000_000
    events = {
        "devices": {"/device:TPU:0": [
            ("%while.1", 10 * ms, 50 * ms),  # holds the two ops below
            ("%bucket_scan_topk_pallas.3", 12 * ms, 20 * ms),
            ("%bucket_scan_topk_pallas.4", 35 * ms, 20 * ms),
            ("%copy.7", 70 * ms, 10 * ms),
            ("%copy.8", 95 * ms, 10 * ms),  # half outside the window
        ]},
        "host": [
            ("bench.window", 0, 100 * ms),
            ("bench.search", 0, 68 * ms),
            ("np.asarray(jax.Array)", 60 * ms, 8 * ms),
        ],
    }
    s = devtrace.summarize(events)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.065)  # 10-60, 70-80, 95-100
    assert s.idle_share == pytest.approx(0.35)
    assert s.op_s["bucket_scan_topk_pallas"] == pytest.approx(0.040)
    assert s.op_s["while"] == pytest.approx(0.010)
    assert s.op_s["copy"] == pytest.approx(0.015)
    # gaps: 0-10 under bench.search, 60-70 under np.asarray, 80-95 under nothing
    assert s.gaps_s == pytest.approx({"bench.search": 0.010, "np.asarray(jax.Array)": 0.010,
                                      "host idle": 0.015})


def test_no_device_ops_reads_nothing():
    assert devtrace.summarize({"devices": {}, "host": [("bench.window", 0, 10)]}) is None


def _kernel_work(sizes, visits, dim, k, lane=128, cmult=128):
    """FLOPs and the fewest bytes of the scan as it is implemented: every
    visited bucket's padded tile on the MXU, and the union of visited
    members read once."""
    dp = dim + (-dim) % lane
    cp = int(sizes.max()) + (-int(sizes.max())) % cmult
    flops = 2.0 * dp * cp * sum(len(v) for v in visits)
    union = set().union(*visits)
    bytes_ = dim * 4 * sum(int(sizes[b]) for b in union) + len(visits) * dim * 4 + len(visits) * k * 8
    return flops, bytes_


@pytest.mark.parametrize("seed", range(8))
def test_roofline_count_never_exceeds_the_work(seed):
    g = np.random.default_rng(seed)
    dim, k = int(g.choice([5, 20, 128])), int(g.choice([1, 10, 100]))
    sizes = g.integers(1, 251, 60)
    visits = [set(g.choice(60, g.integers(1, 40), replace=False).tolist()) for _ in range(100)]
    distances = np.array([sum(int(sizes[b]) for b in v) for v in visits])
    flops, bytes_ = roofline.scan_work(distances, dim, k)
    assert flops == 2.0 * dim * distances.sum()
    true_flops, true_bytes = _kernel_work(sizes, visits, dim, k)
    assert flops <= true_flops and bytes_ <= true_bytes
    # a kernel that did exactly the true work at the chip's peaks reads <= 100%
    fastest = max(true_flops / V5E["flops_bf16"], true_bytes / V5E["hbm_bytes_per_s"])
    share, bound = roofline.roofline_share(flops, bytes_, fastest, V5E["flops_bf16"],
                                           V5E["hbm_bytes_per_s"])
    assert 0.0 < share <= 100.0
    assert bound in ("compute", "memory")


def test_roofline_without_time_reads_nothing():
    assert roofline.roofline_share(1.0, 1.0, 0.0, 1.0, 1.0) is None
