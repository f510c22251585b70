"""Readings that the limits of a training cell's check are set from, in
one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,...
        [--control-seeds ...] [--fault-seeds ...] [--highest-seeds ...]
        [--seconds S] [--out FILE]

For each seed of ``--seeds`` it runs the cell's set-up and check as a run
does (without a window) and prints the compared numbers of the program,
with the loss gap of each step beside them, and whether they are within
the cell's limits.  For each seed of ``--control-seeds`` it makes a whole
run through the harness (``bench.run``, a window of ``--seconds``) with
the control in the program's place (``faults.control``: the reference,
computed in bfloat16), and prints its compared numbers and ``correct``;
for each seed of ``--fault-seeds`` it does the same with each fault of
``chipbench/faults.py`` that the cell can have planted in the program.
For each seed of ``--highest-seeds`` it runs the program with every
float32 matrix product at ``highest`` precision, which shows how much of
the program's gap is the chip's default precision.  The benchmark's own
runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc        # noqa: E402
import importlib  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def _quiet(_name):
    return contextlib.nullcontext()


def numbers(drv, got) -> dict:
    """The compared numbers of readings ``got``, each step's loss gap,
    and whether the numbers are within the cell's limits."""
    nums = drv.numbers(got, drv.want)
    lim = drv.tr["limits"]
    nums["within_limits"] = all(v <= lim[k] for k, v in nums.items())
    nums["loss_gaps"] = [abs(a - b) / abs(b)
                         for a, b in zip(got[0], drv.want[0])]
    return nums


def program_numbers(drv_cls, cell, seed):
    drv = drv_cls(cell, seed)
    t0 = time.perf_counter()
    drv.setup(_quiet)
    t1 = time.perf_counter()
    drv.release()
    gc.collect()
    drv.check()
    t2 = time.perf_counter()
    return {**numbers(drv, drv.readings()), "setup_s": t1 - t0,
            "check_s": t2 - t1}


def harness_run(workload, seed, seconds, root, plant, chip) -> dict:
    """A whole run through the harness with ``plant`` in place: its
    ``correct`` and its compared numbers."""
    from chipbench import bench
    t0 = time.perf_counter()
    with plant():
        result = bench.run(workload, seed, seconds, False, t_start=t0,
                           root=root, require_accelerator=chip)
    return {"correct": result["correct"],
            **{k: c["value"] for k, c in result["checks"].items()},
            "run_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--highest-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="",
                    help="comma-separated faults to plant (default: all "
                         "the cell can have)")
    ap.add_argument("--seconds", type=float, default=1.0,
                    help="window of the control's and the faults' runs")
    ap.add_argument("--out")
    ap.add_argument("--root", default=ROOT,
                    help="checkout whose BENCHMARK.json names the cell")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse without an accelerator")
    args = ap.parse_args(argv)
    import jax
    from chipbench import bench
    from chipbench.faults import FAULTS, control
    cell = bench.load_cell(args.workload, args.root)
    bench.devices_for(cell.chips, not args.allow_cpu)
    bench.enable_compile_cache(args.root)
    name = cell.traffic["driver"]
    drv_cls = importlib.import_module("chipbench.drivers." + name).Driver
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    chip = not args.allow_cpu
    for seed in args.seeds:
        emit({"seed": seed, "kind": "program",
              **program_numbers(drv_cls, cell, seed)})
        gc.collect()
    for seed in args.control_seeds:
        emit({"seed": seed, "kind": "control",
              **harness_run(args.workload, seed, args.seconds, args.root,
                            control, chip)})
        gc.collect()
    for seed in args.highest_seeds:
        with jax.default_matmul_precision("highest"):
            nums = program_numbers(drv_cls, cell, seed)
        emit({"seed": seed, "kind": "program_highest", **nums})
        gc.collect()
    for seed in args.fault_seeds:
        for fault, plant in FAULTS[name].items():
            if args.faults and fault not in args.faults.split(","):
                continue
            emit({"seed": seed, "kind": fault,
                  **harness_run(args.workload, seed, args.seconds,
                                args.root, plant, chip)})
            gc.collect()
    summary = {}
    for row in rows:
        for k, v in row.items():
            if k in ("seed", "kind") or not isinstance(v, (int, float)) \
                    or isinstance(v, bool):
                continue
            s = summary.setdefault(row["kind"], {}).setdefault(k, [v, v])
            s[0], s[1] = min(s[0], v), max(s[1], v)
    print(json.dumps({"summary_min_max": summary,
                      "wall_s": time.perf_counter() - T_START}))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
