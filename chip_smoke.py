"""On-chip smoke run of the main training path.

Trains the paper's CRDNN transducer (``rnnt-crdnn`` at published widths,
random weights from a seed) with PGM selection through the same building
blocks as ``python -m repro.launch.train``: one warm-start epoch on the
full corpus, one resident selection round on the compiled Pallas
kernels, and epochs on the selected subset.  Then it checks what came
out, and compares the kernels and the fused loss with their references
on the chip.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # 4x1 data mesh vs no mesh

Everything runs in this one process.  The last line of stdout is one
JSON object naming the device; it is printed only when every check
passed.  Without a TPU the script exits non-zero before any training.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke.py: {SRC}/repro not found; run it from a "
             f"checkout of the repository")
sys.path.insert(0, SRC)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.configs.base import PGMConfig, TrainConfig       # noqa: E402
from repro.data.pipeline import asr_units                   # noqa: E402
from repro.data.synthetic import make_asr_corpus            # noqa: E402
from repro.kernels.omp_gram.kernel import omp_gram_batched  # noqa: E402
from repro.kernels.omp_gram.ref import omp_gram_batched_ref  # noqa: E402
from repro.kernels.rnnt_lattice.kernel import rnnt_lattice  # noqa: E402
from repro.kernels.rnnt_lattice.ref import rnnt_lattice_ref  # noqa: E402
from repro.launch.cache import enable_compile_cache         # noqa: E402
from repro.launch.mesh import make_mesh                     # noqa: E402
from repro.models.api import build_model                    # noqa: E402
from repro.train.engine import EpochEngine                  # noqa: E402
from repro.train.loop import train_with_selection           # noqa: E402
from repro.train.optim import make_update_for               # noqa: E402

# lattice: max |got - want| / (1 + |want|).  No matmul inside, but the
# TPU's log1p is less exact than the CPU's, so on the chip the kernel and
# the XLA reference each drift ~1e-4 from a float64 oracle over 300 rows
LATTICE_TOL = 1e-3
GRAM_TOL = 1e-2        # max |got - want| / max |want|, fp32 MXU passes
LOSS_TOL = 1e-2        # max relative per-utterance loss error vs CPU fp32
# per-epoch loss agreement, 4x1 mesh vs one device, by platform.  With
# exact fp32 matmuls (CPU) only the reduction order differs.  The TPU's
# default precision rounds matmul inputs to bf16, so a reordered sum can
# move an input by one bf16 step, and AdamW's normalised early updates
# carry that on: the gap is bounded by bf16's unit roundoff instead.
MESH_TOL = {"cpu": 1e-3, "tpu": 2.0 ** -8}


@dataclasses.dataclass(frozen=True)
class Spec:
    """One smoke run: model, corpus shape, and PGM schedule."""
    arch: str = "rnnt-crdnn"
    n_utts: int = 256            # training utterances
    n_val: int = 32
    min_tokens: int = 20
    max_tokens: int = 60         # U; T = max_tokens * frames_per_token
    frames_per_token: int = 20   # 1200 frames (12 s at 100 fps) -> 300
    unit_size: int = 4           # utterances per selection unit
    batch_units: int = 4         # 16 utterances per step
    partitions: int = 4          # divisible by the 4-chip data axis
    subset: float = 0.3
    epochs: int = 3              # warm start, selection round, one more
    warm_start: int = 1
    select_every: int = 2
    lr: float = 1e-3
    seed: int = 0


SMOKE = Spec(arch="rnnt-crdnn-smoke", n_utts=64, n_val=16, min_tokens=4,
             max_tokens=8, frames_per_token=4, batch_units=2, lr=0.05)


class SmokeFailure(RuntimeError):
    pass


def require(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def _corpora(spec: Spec, cfg):
    r = cfg.rnnt
    kw = dict(n_feats=r.n_feats, vocab_size=r.vocab_size,
              min_tokens=spec.min_tokens, max_tokens=spec.max_tokens,
              frames_per_token=spec.frames_per_token)
    units = asr_units(make_asr_corpus(spec.seed, spec.n_utts, **kw),
                      spec.unit_size)
    val = asr_units(make_asr_corpus(spec.seed + 7, spec.n_val, **kw),
                    spec.unit_size)
    return units, val


def _train_config(spec: Spec) -> TrainConfig:
    return TrainConfig(
        lr=spec.lr, optimizer="adamw", epochs=spec.epochs, seed=spec.seed,
        pgm=PGMConfig(subset_fraction=spec.subset,
                      n_partitions=spec.partitions,
                      select_every=spec.select_every,
                      warm_start_epochs=spec.warm_start))


class CompileClock:
    """Sums JAX's backend-compile durations while registered."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def train(spec: Spec, *, mesh=None, log=print) -> dict:
    """One PGM training run; checks what holds on every platform and
    returns the run's facts."""
    cfg = get_config(spec.arch)
    bundle = build_model(cfg)
    units, val = _corpora(spec, cfg)
    tc = _train_config(spec)
    t0 = time.perf_counter()
    h = train_with_selection(
        bundle, units, tc, method="pgm", val_units=val,
        batch_units=spec.batch_units, engine="scan",
        resident_selection=True, mesh=mesh,
        log_fn=lambda s: log("  " + s))
    wall = time.perf_counter() - t0
    require(len(h.train_loss) == spec.epochs,
            f"{len(h.train_loss)} epochs ran, expected {spec.epochs}")
    require(all(math.isfinite(x) for x in h.train_loss + h.val_loss),
            f"non-finite loss: train {h.train_loss} val {h.val_loss}")
    require(h.train_loss[-1] < h.train_loss[0],
            f"train loss did not fall: {h.train_loss}")
    require(len(h.selections) >= 1, "no selection round ran")
    n_sel = sum(1 for i in h.selections[-1]["indices"] if i >= 0)
    require(n_sel > 0, "selection round picked no unit")
    require(h.degraded_rounds == 0,
            f"{h.degraded_rounds} selection round(s) degraded")
    return {"train_loss": h.train_loss, "val_loss": h.val_loss,
            "selected_units": n_sel, "n_units": units["feats"].shape[0],
            "selection_kernels": h.selection_kernels,
            "indices": h.selections[-1]["indices"], "wall_s": wall,
            "n_params": sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(h.final_params))}


def epoch_program(spec: Spec, *, mesh=None) -> dict:
    """Compile the engine's one-epoch dispatch at the run's shapes and
    report whether a Pallas kernel is inside and its memory analysis;
    with a mesh, also where the resident units and a step's batch live."""
    cfg = get_config(spec.arch)
    bundle = build_model(cfg)
    units, val = _corpora(spec, cfg)
    tc = _train_config(spec)
    eng = EpochEngine(bundle, tc, units, val_units=val,
                      batch_units=spec.batch_units, mesh=mesh)
    opt_init, _ = make_update_for(tc)
    params = eng.bundle.init_params(jax.random.PRNGKey(spec.seed))
    opt_state = opt_init(params)
    params, opt_state = eng.shard_state(params, opt_state)
    plan = eng.full_plan(0)
    compiled = eng.lower_epoch(params, opt_state, tc.lr, plan).compile()
    out = {"custom_call": "tpu_custom_call" in compiled.as_text(),
           "memory": compiled.memory_analysis()}
    batch = jax.jit(eng.gather_batch)(eng.units, plan[0][0])
    for name, x in (("units", eng.units["feats"]), ("batch", batch["feats"])):
        out[f"{name}_devices"] = len(x.sharding.device_set)
        out[f"{name}_rows_on_device0"] = min(
            s.data.shape[0] for s in x.addressable_shards
            if s.device == jax.devices()[0])
        out[f"{name}_rows"] = x.shape[0]
    return out


def _lattice_inputs(T, B, U1, seed):
    """Random lattice rows with the kernel's invariants: emit[..., 0] is
    NEG, sparse additive seeds as the alpha/beta passes make them."""
    rng = np.random.default_rng(seed)
    neg = -1e30
    mult = rng.normal(size=(T, B, U1)).astype(np.float32)
    add = np.where(rng.uniform(size=(T, B, U1)) < 0.3,
                   rng.normal(size=(T, B, U1)), neg).astype(np.float32)
    emit = rng.normal(size=(T, B, U1)).astype(np.float32)
    emit[:, :, 0] = neg
    return jnp.asarray(mult), jnp.asarray(add), jnp.asarray(emit)


def compare_kernels(spec: Spec, *, interpret: bool) -> dict:
    """The lattice and Gram kernels against their references, on the
    default device, at the shapes the run used."""
    cfg = get_config(spec.arch)
    T = spec.max_tokens * spec.frames_per_token // cfg.rnnt.time_reduction
    B = spec.batch_units * spec.unit_size
    m, a, e = _lattice_inputs(T, B, spec.max_tokens + 1, spec.seed)
    got = jax.jit(lambda m, a, e: rnnt_lattice(
        m, a, e, interpret=interpret))(m, a, e)
    want = jax.jit(rnnt_lattice_ref)(m, a, e)
    lat = float(jnp.max(jnp.abs(got - want) / (1.0 + jnp.abs(want))))
    n_units = spec.n_utts // spec.unit_size
    per = n_units // spec.partitions
    pc = _train_config(spec).pgm
    D = pc.sketch_dim_h * pc.sketch_dim_v     # stage-A sketch width
    g = jax.random.normal(jax.random.PRNGKey(spec.seed),
                          (spec.partitions, per, D), jnp.float32)
    got = jax.jit(lambda g: omp_gram_batched(g, interpret=interpret))(g)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(omp_gram_batched_ref)(g)
    gram = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    return {"lattice_shape": (T, B, spec.max_tokens + 1), "lattice_err": lat,
            "gram_shape": (spec.partitions, per, D), "gram_err": gram}


def compare_loss(spec: Spec) -> dict:
    """The fused loss at the initial parameters on one 4-utterance unit
    on the default device, against the dense oracle jitted on the CPU."""
    cfg = get_config(spec.arch)
    fused = build_model(cfg)
    dense = build_model(dataclasses.replace(
        cfg, rnnt=dataclasses.replace(cfg.rnnt, loss_impl="dense")))
    units, _ = _corpora(spec, cfg)
    batch = {k: jnp.asarray(v[0]) for k, v in units.items()}
    params = fused.init_params(jax.random.PRNGKey(spec.seed))
    got = np.asarray(jax.jit(fused.per_example_loss)(params, batch))
    cpu = jax.devices("cpu")[0]
    # committed CPU inputs place the jit on the CPU; the dense oracle
    # never dispatches to a Pallas kernel, whatever the default backend
    want = np.asarray(jax.jit(dense.per_example_loss)(
        jax.device_put(params, cpu), jax.device_put(batch, cpu)))
    err = float(np.max(np.abs(got - want) / np.abs(want)))
    return {"loss_fused": got.tolist(), "loss_dense_cpu": want.tolist(),
            "loss_err": err}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _memory(ma) -> dict:
    return {k: getattr(ma, f"{k}_size_in_bytes") for k in
            ("argument", "output", "temp", "generated_code")}


def one_chip(spec: Spec, clock: CompileClock, *, interpret: bool = False,
             log=print) -> dict:
    """Train once and compare kernels and loss with their references;
    returns what only the platform can decide (kernel backends)."""
    prog = epoch_program(spec)
    log(f"epoch program: tpu_custom_call={prog['custom_call']} "
        f"memory {_memory(prog['memory'])}")
    run = train(spec, log=log)
    log(f"parameters: {run['n_params']}")
    for i, (tl, vl) in enumerate(zip(run["train_loss"], run["val_loss"])):
        log(f"epoch {i}: train {tl!r} val {vl!r}")
    log(f"selected {run['selected_units']} of {run['n_units']} units on "
        f"{run['selection_kernels']} selection kernels")
    log(f"train wall {run['wall_s']!r} s, compile {clock.seconds!r} s, "
        f"peak bytes {_peak_bytes()}")
    kern = compare_kernels(spec, interpret=interpret)
    log(f"lattice {kern['lattice_shape']} vs ref: {kern['lattice_err']!r} "
        f"(tol {LATTICE_TOL})")
    log(f"gram {kern['gram_shape']} vs ref: {kern['gram_err']!r} "
        f"(tol {GRAM_TOL})")
    require(kern["lattice_err"] <= LATTICE_TOL, "lattice kernel off its ref")
    require(kern["gram_err"] <= GRAM_TOL, "gram kernel off its ref")
    loss = compare_loss(spec)
    log(f"fused loss on {jax.devices()[0].platform} {loss['loss_fused']} vs "
        f"dense on cpu {loss['loss_dense_cpu']}: {loss['loss_err']!r} "
        f"(tol {LOSS_TOL})")
    require(loss["loss_err"] <= LOSS_TOL, "fused loss off the dense oracle")
    return {"custom_call": prog["custom_call"],
            "selection_kernels": [run["selection_kernels"]]}


def four_chips(spec: Spec, clock: CompileClock, *, log=print) -> dict:
    """The same run on a 4x1 data mesh and on one device, compared."""
    mesh = make_mesh((4, 1), ("data", "model"), devices=jax.devices()[:4])
    prog = epoch_program(spec, mesh=mesh)
    log(f"4x1 epoch program: memory per device {_memory(prog['memory'])}")
    for name in ("units", "batch"):
        log(f"{name}: {prog[f'{name}_rows']} rows over "
            f"{prog[f'{name}_devices']} devices, "
            f"{prog[f'{name}_rows_on_device0']} on device 0")
        require(prog[f"{name}_devices"] == 4,
                f"{name} on {prog[f'{name}_devices']} devices, not 4")
        require(prog[f"{name}_rows_on_device0"] < prog[f"{name}_rows"],
                f"all {name} rows sit on device 0")
    single = train(spec, log=log)
    sharded = train(spec, mesh=mesh, log=log)
    worst = 0.0
    for i, (t1, t4, v1, v4) in enumerate(zip(
            single["train_loss"], sharded["train_loss"],
            single["val_loss"], sharded["val_loss"])):
        rel = max(abs(t4 - t1) / abs(t1), abs(v4 - v1) / abs(v1))
        worst = max(worst, rel)
        log(f"epoch {i}: 1 device train {t1!r} val {v1!r} | 4x1 mesh "
            f"train {t4!r} val {v4!r} | rel {rel!r}")
    log(f"selection: 1 device {single['selected_units']} units, 4x1 mesh "
        f"{sharded['selected_units']} units, same indices "
        f"{single['indices'] == sharded['indices']}")
    tol = MESH_TOL[jax.devices()[0].platform]
    log(f"worst relative loss gap {worst!r} (tol {tol}); compile "
        f"{clock.seconds!r} s, peak bytes on device 0 {_peak_bytes()}")
    require(worst <= tol, "4x1 mesh losses off the one-device run")
    return {"custom_call": prog["custom_call"],
            "selection_kernels": [single["selection_kernels"],
                                  sharded["selection_kernels"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the 4x1 data-mesh trainer against the same "
                         "run on one device (needs four chips)")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke.py: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{enable_compile_cache()}")
    clock = CompileClock()
    facts = (four_chips if args.four_chips else one_chip)(Spec(), clock)
    require(facts["custom_call"], "no Pallas kernel in the compiled epoch")
    require(all(k == "pallas" for k in facts["selection_kernels"]),
            f"selection ran on {facts['selection_kernels']}, not pallas")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
