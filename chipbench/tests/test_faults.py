"""With the timed path broken underneath, a run comes out not correct.

Each test drives a whole run of a cell (data, Session, warm-up, window,
check) on the CPU at a cut size, past the harness's look for a chip,
with one fault of `chipbench/faults.py` planted.
"""
import time

import pytest

from chipbench import faults, run

SIZES = {"criteo-1chip": 4096, "higgs-1chip": 8192}


def _run(name, fault, n):
    return run.run_cell(run.load_cell(name), seed=21, seconds=0.0,
                        trace=False, t_start=time.perf_counter(),
                        require_tpu=False, fault=fault, n=n)


@pytest.mark.parametrize("name", sorted(SIZES))
def test_sound_run_is_correct(name):
    res = _run(name, None, SIZES[name])
    assert res["correct"] and res["failed"] == 0, res["checks"]


@pytest.mark.parametrize("name,fault", [
    (name, fault) for name in sorted(SIZES) for fault in faults.FAULTS])
def test_fault_is_not_correct(name, fault):
    res = _run(name, faults.FAULTS[fault], SIZES[name])
    assert not res["correct"], res["checks"]

