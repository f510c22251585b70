"""``calibrate.py`` for the cells whose traffic names the
``train_by_config`` driver, which plants the ``train`` driver's faults
and control in them.

    python3 chipbench/calibrate_by_config.py --workload <cell> --seeds ...
        [calibrate.py's other options]

``calibrate.py`` looks a cell's faults up by its driver's name in
``faults.FAULTS``; this adds the ``train_by_config`` driver there, with
the ``train`` driver's faults (both fault the program, and the control
patches ``train.Driver.readings``, which the driver inherits), and runs
it.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import calibrate, faults  # noqa: E402

faults.FAULTS.setdefault("train_by_config", faults.FAULTS["train"])

if __name__ == "__main__":
    sys.exit(calibrate.main())
