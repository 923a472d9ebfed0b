"""The benchmark's comparison passes the store and fails the control.

Each cell runs at tiny sizes on the CPU twice: with the store under
test (`correct` true) and with the control in its place, the plain
reference that keeps the first write of a key (`correct` false).
"""
import pytest

from bench_tpu_tiny import CELLS, correct, run_tiny


@pytest.mark.parametrize("cell", CELLS)
def test_store_is_correct(cell):
    run, checks = run_tiny(cell)
    assert correct(run, checks), (checks, run.error)
    assert run.attempted > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    run, checks = run_tiny(cell, control=True)
    assert run.error is None
    assert not correct(run, checks), checks

