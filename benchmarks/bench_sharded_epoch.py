"""Sharded scanned-epoch benchmark: steady-state epoch throughput of the
single-device scan engine vs the mesh-native engine on a simulated
4-device host mesh (2x2 data x model), plus the dispatch overhead saved
by multi-epoch chunking (``run_epochs`` over 4 epochs vs 4 per-epoch
dispatches).

The measurement runs in a subprocess because the 4 host devices must be
forced via ``XLA_FLAGS`` before jax initializes; the parent parses one
JSON line and writes ``BENCH_sharded_epoch.json`` at the repo root.

Methodology (DESIGN.md §7): variants are interleaved round by round so
they sample the same container state, warmup rounds pay compile +
allocator effects, per-variant headlines are best-of over rounds, and
speedups are medians of per-round ratios.  On a CPU host the "4-device
mesh" shares one socket, so sharded throughput *below* 1x is expected —
the number tracks partitioning overhead trends, not real-mesh scaling.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, List

_CHILD = """
import json, time
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
ROUNDS, WARMUP, CHUNK = 4, 2, 4

cfg = get_config("starcoder2-3b-smoke")
bundle = build_model(cfg)
units = lm_units(make_lm_corpus(0, N_EX, SEQ, cfg.vocab_size,
                                hard_fraction=0.4), unit_size=UNIT)
tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=1, pgm=PGMConfig())
opt_init, _ = make_update_for(tc)
mesh = make_mesh((2, 2), ("data", "model"))

engines = {
    "scan": EpochEngine(bundle, tc, units, batch_units=BATCH_UNITS),
    "sharded": EpochEngine(bundle, tc, units, batch_units=BATCH_UNITS,
                           mesh=mesh),
}
state = {}
for name, eng in engines.items():
    p = bundle.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    state[name] = eng.shard_state(p, o)

def epoch(name, e):
    eng = engines[name]
    p, o = state[name]
    p, o, losses = eng.run_epoch(p, o, tc.lr, eng.full_plan(e))
    jax.block_until_ready(losses)
    state[name] = (p, o)
    return int(losses.shape[0])

# chunk-dispatch benchmark state: two more single-device engines so the
# chunked and per-epoch executables both stay warm
for name in ("perepoch", "chunked"):
    eng = EpochEngine(bundle, tc, units, batch_units=BATCH_UNITS)
    p = bundle.init_params(jax.random.PRNGKey(0))
    engines[name] = eng
    state[name] = (p, opt_init(p))

def perepoch(e0):
    eng = engines["perepoch"]
    p, o = state["perepoch"]
    steps = 0
    for e in range(e0, e0 + CHUNK):
        p, o, losses = eng.run_epochs(p, o, tc.lr, float("inf"),
                                      [eng.full_plan(e)])[:3]
        steps += int(losses.shape[-1])
    jax.block_until_ready(losses)
    state["perepoch"] = (p, o)
    return steps

def chunked(e0):
    eng = engines["chunked"]
    p, o = state["chunked"]
    plans = [eng.full_plan(e) for e in range(e0, e0 + CHUNK)]
    p, o, losses = eng.run_epochs(p, o, tc.lr, float("inf"), plans)[:3]
    jax.block_until_ready(losses)
    state["chunked"] = (p, o)
    return int(np.prod(losses.shape))

for r in range(WARMUP):
    epoch("scan", r); epoch("sharded", r)
    perepoch(r * CHUNK); chunked(r * CHUNK)

rates = {k: [] for k in engines}
for r in range(WARMUP, WARMUP + ROUNDS):
    for name, fn in (("scan", lambda: epoch("scan", r)),
                     ("sharded", lambda: epoch("sharded", r)),
                     ("perepoch", lambda: perepoch(r * CHUNK)),
                     ("chunked", lambda: chunked(r * CHUNK))):
        t0 = time.time()
        steps = fn()
        rates[name].append(steps / (time.time() - t0))

out = {name + "_steps_per_s": max(rs) for name, rs in rates.items()}
out["sharded_over_scan_speedup"] = float(np.median(
    [s / h for h, s in zip(rates["scan"], rates["sharded"])]))
out["chunked_over_perepoch_speedup"] = float(np.median(
    [c / p for p, c in zip(rates["perepoch"], rates["chunked"])]))
print("BENCH_JSON=" + json.dumps(out))
"""


def bench_sharded_epoch() -> List[Dict]:
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
    out_path = os.path.join(root, "BENCH_sharded_epoch.json")
    with open(out_path, "w") as f:
        json.dump({k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in rec_out.items()}, f, indent=2)
    print(f"# wrote {os.path.normpath(out_path)}", file=sys.stderr)

    rows = []
    for name in ("scan", "sharded", "perepoch", "chunked"):
        sps = rec[name + "_steps_per_s"]
        rows.append({"name": f"sharded_epoch/{name}",
                     "us_per_call": 1e6 / sps,
                     "derived": f"steps_per_s={sps:.1f}",
                     "steps_per_s": sps})
    for key, label in (("sharded_over_scan_speedup", "sharded_over_scan"),
                       ("chunked_over_perepoch_speedup",
                        "chunked_over_perepoch")):
        rows.append({"name": f"sharded_epoch/{label}", "us_per_call": 0.0,
                     "derived": f"{label}={rec[key]:.2f}x",
                     "steps_per_s": 0.0, "speedup": rec[key]})
    return rows


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    for r in bench_sharded_epoch():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
