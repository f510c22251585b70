"""Compressed pod-collective step benchmark: steady-state epoch
throughput of the two-level ``data x pod`` engine (explicit
``compressed_psum`` on the pod axis inside the scan — modes ``none`` /
``bf16`` / ``topk``) against the GSPMD-only ``data x model`` engine on a
simulated 4-device host mesh.

The measurement runs in a subprocess because the 4 host devices must be
forced via ``XLA_FLAGS`` before jax initializes; the parent parses one
JSON line and writes ``BENCH_compressed_step.json`` at the repo root.

Methodology (DESIGN.md §7): variants interleave round by round so they
sample the same container state, warmup rounds pay compile + allocator
effects, per-variant headlines are best-of over rounds, and speedups are
medians of per-round ratios.  On one CPU socket the pod collective is a
memory shuffle, not a DCN wire, so the mode-over-GSPMD ratios track the
*overhead* of the restructured step (per-pod vmap + explicit collective
+ top-k selection), not real cross-pod bandwidth wins — the wire-width
claim itself is a compiler fact asserted by
``tests/test_compressed_engine.py`` on the lowered HLO.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

_CHILD = """
import dataclasses, json, time
from repro.launch.mesh import make_mesh
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import lm_units
from repro.data.synthetic import make_lm_corpus
from repro.models.api import build_model
from repro.train.engine import EpochEngine
from repro.train.optim import make_update_for

N_EX, SEQ, UNIT, BATCH_UNITS = 64, 8, 1, 4
ROUNDS, WARMUP = 4, 2

cfg = get_config("starcoder2-3b-smoke")
bundle = build_model(cfg)
units = lm_units(make_lm_corpus(0, N_EX, SEQ, cfg.vocab_size,
                                hard_fraction=0.4), unit_size=UNIT)
base = TrainConfig(lr=0.5, optimizer="sgd", epochs=1, pgm=PGMConfig())
gspmd_mesh = make_mesh((2, 2), ("data", "model"))
pod_mesh = make_mesh((2, 2), ("data", "pod"))

variants = {
    "gspmd": (base, gspmd_mesh),
    "pod_none": (dataclasses.replace(base, compress_mode="none"), pod_mesh),
    "pod_bf16": (dataclasses.replace(base, compress_mode="bf16"), pod_mesh),
    "pod_topk": (dataclasses.replace(base, compress_mode="topk",
                                     compress_k_frac=0.05), pod_mesh),
}
engines, state = {}, {}
for name, (tc, mesh) in variants.items():
    eng = EpochEngine(bundle, tc, units, batch_units=BATCH_UNITS, mesh=mesh)
    opt_init, _ = make_update_for(tc)
    p = bundle.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    engines[name] = (eng, tc)
    state[name] = eng.shard_state(p, o)

def epoch(name, e):
    eng, tc = engines[name]
    p, o = state[name]
    p, o, losses = eng.run_epoch(p, o, tc.lr, eng.full_plan(e))
    jax.block_until_ready(losses)
    state[name] = (p, o)
    return int(losses.shape[0])

for r in range(WARMUP):
    for name in variants:
        epoch(name, r)

rates = {k: [] for k in variants}
for r in range(WARMUP, WARMUP + ROUNDS):
    for name in variants:
        t0 = time.time()
        steps = epoch(name, r)
        rates[name].append(steps / (time.time() - t0))

out = {name + "_steps_per_s": max(rs) for name, rs in rates.items()}
for name in ("pod_none", "pod_bf16", "pod_topk"):
    out[name + "_over_gspmd"] = float(np.median(
        [s / g for g, s in zip(rates["gspmd"], rates[name])]))
print("BENCH_JSON=" + json.dumps(out))
"""


def bench_compressed_step() -> List[Dict]:
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    # a forced-host-device CPU measurement: pinned to the CPU so the
    # child never competes with its parent for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, env=env, timeout=900)
    if p.returncode != 0:
        raise RuntimeError(p.stderr[-2000:])
    line = next(l for l in p.stdout.splitlines()
                if l.startswith("BENCH_JSON="))
    rec = json.loads(line[len("BENCH_JSON="):])

    import time
    rec_out = dict(rec, time=time.time())
    out_path = os.path.join(root, "BENCH_compressed_step.json")
    with open(out_path, "w") as f:
        json.dump({k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in rec_out.items()}, f, indent=2)
    print(f"# wrote {os.path.normpath(out_path)}", file=sys.stderr)

    rows = []
    for name in ("gspmd", "pod_none", "pod_bf16", "pod_topk"):
        sps = rec[name + "_steps_per_s"]
        rows.append({"name": f"compressed_step/{name}",
                     "us_per_call": 1e6 / sps,
                     "derived": f"steps_per_s={sps:.1f}",
                     "steps_per_s": sps})
    for name in ("pod_none", "pod_bf16", "pod_topk"):
        key = name + "_over_gspmd"
        rows.append({"name": f"compressed_step/{key}", "us_per_call": 0.0,
                     "derived": f"{key}={rec[key]:.2f}x",
                     "steps_per_s": 0.0, "speedup": rec[key]})
    return rows


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    for r in bench_compressed_step():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
