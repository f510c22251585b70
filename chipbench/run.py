"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each compared number with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("run.py: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    from chipbench.bench import NoAccelerator, run
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=T_START)
    except NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
