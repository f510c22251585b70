"""The control of the cell's check, through the harness at a small size:
the reference, its network computed in bfloat16 (the precision below the
configuration's float32), takes the program's place, and the run, held
to the cell's own limits, comes out not correct.  The program, run the
same way, stays correct (``test_harness.py``)."""
import time

import pytest

from chipbench import bench, faults
from chipbench.tests import smoke

CELL = "crdnn.train_subset"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(str(tmp_path_factory.mktemp("control")),
                           **smoke.CONTROL_SIZE)


@pytest.mark.parametrize("seed", [3, 2 ** 33 + 1])
def test_control_is_not_correct(root, seed):
    with faults.control():
        result = bench.run(CELL, seed, 0.2, False,
                           t_start=time.perf_counter(), root=root,
                           require_accelerator=False, compile_cache=False,
                           log=lambda s: None)
    assert not result["correct"], result["checks"]
    assert result["failed"] == 0 and result["checks"]["failed_units"][
        "value"] == 0
