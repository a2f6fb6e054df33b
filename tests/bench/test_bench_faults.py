"""The check that decides ``correct`` fails a broken timed path: the
harness drives the tiny cell on the CPU with the program's step broken
underneath, once for each fault a training cell can have."""
import bench_tiny
import pytest


@pytest.mark.parametrize("fault,number", [
    ("unchanged", "change_gap"),     # the step returns its state as it was
    ("half_batch", "loss_gap.0"),    # half the rows, the mean over the rest
    # the same, in the plain step program alone: the one the window times
    ("unchanged.plain", "change_gap"),
    ("half_batch.plain", "grad1_gap"),
])
def test_fault_is_not_correct(fault, number):
    res = bench_tiny.run(bench_tiny.cell(), fault=fault)
    assert res["correct"] is False
    c = res["check"][number]
    assert c["value"] > c["limit"], res["check"]
