"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints ``name,us_per_call,derived`` CSV — one row per measured cell, one
section per paper table/figure (benchmarks/tables.py), plus kernel
micro-benchmarks, the train-loop engine benchmark and the
selection-round/rnnt-loss/sharded-epoch benchmarks (also written to
``BENCH_train_loop.json`` / ``BENCH_selection_round.json`` /
``BENCH_rnnt_loss.json`` / ``BENCH_sharded_epoch.json`` at the repo
root so PRs can track the trajectory) and (when dry-run artifacts
exist) the roofline table.
REPRO_BENCH_SCALE=micro|small scales corpus/epoch counts.
"""
from __future__ import annotations

import json
import os
import sys
import time


def main() -> int:
    """Run every phase; exit status 1 when any printed an ERROR row."""
    t_start = time.time()
    errors = []

    def error_row(name, e):
        print(f"{name},0,ERROR={type(e).__name__}:{e}")
        errors.append(name)

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    from benchmarks.tables import ALL_TABLES
    from benchmarks.bench_kernels import bench_kernels
    from benchmarks.bench_train_loop import bench_train_loop

    print("name,us_per_call,derived")

    # static-contract gate duration: `make check-static` runs on every
    # `make test-fast`, so its wall time is part of the dev loop and is
    # tracked like any other cell
    try:
        from pathlib import Path

        from repro.analysis import all_rules, run_lint
        t0 = time.time()
        findings = run_lint(Path(__file__).resolve().parent.parent)
        dt = time.time() - t0
        print(f"check_static/full_repo,{dt*1e6:.1f},"
              f"findings={len(findings)};rules={len(all_rules())}")
    except Exception as e:
        error_row("check_static", e)

    for fn in ALL_TABLES:
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # report the failure, run the other phases
            error_row(fn.__name__, e)
            continue
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        print(f"# {fn.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    for r in bench_kernels():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    # engine + selection-round benchmarks, each with a JSON trajectory
    # artifact at the repo root
    def run_json_bench(fn, out_name, value_key, value_suffix, speedup_key):
        try:
            rows = fn()
        except Exception as e:
            error_row(fn.__name__, e)
            return
        record = {"time": time.time()}
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            key = r["name"].split("/", 1)[1]
            if r[value_key]:
                record[key + value_suffix] = round(r[value_key], 2)
            if "speedup" in r:
                # rows may carry their own key (kernel-on/off and
                # chol-vs-dense deltas next to the headline speedup)
                record[r.get("speedup_key", speedup_key)] = \
                    round(r["speedup"], 3)
        out = os.path.join(os.path.dirname(__file__), "..", out_name)
        with open(out, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {os.path.normpath(out)}", file=sys.stderr)

    def _bench_selection_round():
        # deferred import so a broken bench module reports as an ERROR row
        # instead of aborting the harness before the other benchmarks
        from benchmarks.bench_selection_round import bench_selection_round
        return bench_selection_round()
    _bench_selection_round.__name__ = "bench_selection_round"

    run_json_bench(bench_train_loop, "BENCH_train_loop.json",
                   "steps_per_s", "_steps_per_s", "scan_over_host_speedup")
    run_json_bench(_bench_selection_round, "BENCH_selection_round.json",
                   "round_ms", "_round_ms", "resident_over_host_speedup")

    # benchmarks that write their own BENCH_*.json (multiple speedup /
    # memory keys per record): the RNN-T loss path comparison and the
    # sharded/chunked epoch benchmark (4-device subprocess)
    def run_self_writing_bench(mod_name, fn_name):
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=[fn_name])
            for r in getattr(mod, fn_name)():
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            print(f"# wrote BENCH artifact of {mod_name}", file=sys.stderr)
        except Exception as e:
            error_row(fn_name, e)

    run_self_writing_bench("bench_rnnt_loss", "bench_rnnt_loss")
    run_self_writing_bench("bench_sharded_epoch", "bench_sharded_epoch")
    run_self_writing_bench("bench_compressed_step", "bench_compressed_step")
    run_self_writing_bench("bench_serve", "bench_serve")
    run_self_writing_bench("bench_archs", "bench_archs")

    # selection-round roofline (DESIGN.md §9): compile the round with
    # kernels on vs off and analyze the optimized HLO — reproducible
    # here with no artifacts needed
    try:
        from repro.launch.roofline import selection_round_records
        for rec in selection_round_records():
            t = rec["terms"]
            print(f"roofline/{rec['variant']},{t['bound_s']*1e6:.1f},"
                  f"dom={t['dominant']};flops={rec['flops']:.3e};"
                  f"hbm_bytes={rec['bytes_accessed']:.3e}")
    except Exception as e:
        error_row("selection_round_records", e)

    # roofline table from dry-run artifacts, if the sweep has run
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "dryrun")
    if os.path.isdir(art) and any(f.endswith(".json")
                                  for f in os.listdir(art)):
        from repro.launch.roofline import load_artifacts
        for rec in load_artifacts(art):
            t = rec["terms"]
            print(f"roofline/{rec['arch']}@{rec['shape']}@{rec['mesh']},"
                  f"{t['bound_s']*1e6:.1f},"
                  f"dom={t['dominant']};roofline={100*t['roofline_fraction']:.1f}%;"
                  f"useful={t['useful_ratio'] and round(t['useful_ratio'],2)}")
    print(f"# total {time.time()-t_start:.1f}s", file=sys.stderr)
    if errors:
        print(f"# {len(errors)} phase(s) failed: {', '.join(errors)}",
              file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
