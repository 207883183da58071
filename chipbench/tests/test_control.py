"""The control fails the check, and its float32 twin passes it.

The reference solver put in the program's place in bfloat16 (the step
below the configurations' float32) must come out not correct; the same
solver in float32 is the witness that the check fails the precision and
not the solver.  Sizes are cut for a test run; `chipbench/control.py`
reads the same numbers on the chip at the cells' own sizes.
"""
import pytest

from chipbench import control, run

SIZES = {"criteo-1chip": 4096, "higgs-1chip": 16384}


@pytest.mark.parametrize("name", sorted(SIZES))
def test_bfloat16_control_is_not_correct(name):
    out = control.control_readings(run.load_cell(name), 11, "bfloat16",
                                   n=SIZES[name])
    assert not out["correct"], out


@pytest.mark.parametrize("name", sorted(SIZES))
def test_float32_witness_is_correct(name):
    out = control.control_readings(run.load_cell(name), 11, "float32",
                                   n=SIZES[name])
    assert out["correct"], out


def test_safe_batch():
    assert control.safe_batch(1e-3, 262144) == 256
    assert control.safe_batch(1e-3, 2097152) == 2048
    assert control.safe_batch(1e-3, 512) == 1
