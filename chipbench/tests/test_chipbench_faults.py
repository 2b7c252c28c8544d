"""Whole runs with the timed path broken underneath: ``correct`` must come
out false for each fault a cell can have, and for the control, and true
without one.  The runs skip the harness's look for a chip and run on the CPU,
at a shrunken size."""
import contextlib
import copy
import time
from types import SimpleNamespace

import pytest

from chipbench import faults, harness


def _run(cell, fault=None, seconds=0.5, shrink=None):
    found = harness.load_cell(cell)
    if shrink:
        found = copy.deepcopy(found)
        shrink(found)
    args = SimpleNamespace(workload=cell, seed=2**31 + 11, seconds=seconds,
                           trace=0)
    plant = (faults.FAULTS[found["traffic"]["driver"]][fault] if fault
             else contextlib.nullcontext)
    with plant():
        return harness.run_cell(args, time.perf_counter(), None, found)


def _tiny(found):
    found["config"]["model"].update(n_layers=2, d_model=64, vocab_size=512,
                                    ssm_state=16, ssm_head_dim=16,
                                    ssm_chunk=32)
    found["traffic"].update(batch=4, seq=64, vocab_used=500)


@pytest.mark.parametrize("fault", [None, "unchanged", "half"])
def test_train_faults_are_caught(fault):
    res = _run("train-mamba2-370m-k1", fault, shrink=_tiny)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


def test_train_control_is_caught():
    """The control (the reference with float8 where the system holds
    bfloat16) in the program's place fails the limits."""
    found = copy.deepcopy(harness.load_cell("train-mamba2-370m-k1"))
    _tiny(found)
    cell = harness.driver_class(found["traffic"])(
        found["config"], found["traffic"], 2**31 + 12, 0.5)
    rec = cell.run_window(lambda name: contextlib.nullcontext())
    nums, _ = cell.check(rec, control=True)
    assert any(v > cell.limits[k] for k, v in nums.items()), nums
