"""The harness end to end on the CPU at smoke size: every cell's run is
correct, each fault a cell can have, planted in the program, makes it
not correct, and a checkout without the program or without a chip gives
no result."""
import os
import subprocess
import sys
import time

import pytest

from chipbench import bench, faults
from chipbench.tests import smoke

CELLS = {"crdnn.train_subset": "train"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return smoke.make_root(str(tmp_path_factory.mktemp("smoke")))


def _run(root, cell, seed=2 ** 32 + 5):
    return bench.run(cell, seed, 0.2, False, t_start=time.perf_counter(),
                     root=root, require_accelerator=False,
                     compile_cache=False, log=lambda s: None)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_is_correct(root, cell):
    result = _run(root, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {
        m["name"] for m in bench.load_cell(cell, root).end_to_end}


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell, drv in sorted(CELLS.items())
    for fault in sorted(faults.FAULTS[drv])])
def test_fault_is_not_correct(root, cell, fault):
    with faults.FAULTS[CELLS[cell]][fault]():
        result = _run(root, cell)
    assert not result["correct"], result["checks"]


def test_no_result_without_program_or_chip(tmp_path):
    only = smoke.copy_benchmark_only(str(tmp_path))
    cmd = [sys.executable, "chipbench/run.py", "--workload",
           "crdnn.train_subset", "--seed", "1", "--seconds", "1"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(cmd, cwd=only, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and not out.stdout.strip()
    out = subprocess.run(cmd, cwd=smoke.REPO, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and not out.stdout.strip()
    assert "needs a TPU" in out.stderr
