"""The control -- the reference in the program's place, its cross term in
three bf16 passes -- fails the limits that the program's own answers pass.

On the chip the same comparison runs at each cell's full size through
``bench/calibrate.py``; here the cells are cut to a CPU test's size."""
import pytest

import calibrate
from small import small_cell


@pytest.mark.parametrize("workload", ["db1-exact-k10", "ward-exact-k10"])
@pytest.mark.parametrize("seed", [3, 2**31 + 7, 40_000_000_019])
def test_control_fails_where_the_program_passes(workload, seed):
    cell = small_cell(workload)
    limits = cell["config"]["limits"]
    got = calibrate.readings(cell, seed, seconds=0.5)
    assert all(got["program"][n] <= lim for n, lim in limits.items()), got["program"]
    assert any(got["control"][n] > lim for n, lim in limits.items()), got["control"]
