"""Chaos harness (DESIGN.md §10): every deterministic fault injector in
``train/faults.py`` must recover along its documented path.

The guard's exactness contract anchors the suite: a non-finite step
gated off in-scan is bit-identical to training the same schedule with
that batch as a padding row (``FaultPlan(drop_step=...)`` builds exactly
that fault-free reference run), so the faulted LM-smoke run's final val
loss matches its fault-free reference to 0.0 — well within the 1e-3
acceptance tolerance.  The transparent faults (prefetch crash,
preemption + resume, corrupt-checkpoint fallback, kernel fallback)
reproduce the *unfaulted* trajectory outright.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.contracts import assert_retrace_free
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.core.lastlayer import make_proj_for
from repro.core.pgm import ResidentSelector
from repro.data.pipeline import lm_units
from repro.data.synthetic import make_lm_corpus
from repro.models.api import build_model
from repro.train import checkpoint as ckpt_mod
from repro.train import faults
from repro.train.engine import EpochEngine
from repro.train.loop import train_with_selection
from repro.train.optim import make_update_for

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def lm():
    cfg = get_config("starcoder2-3b-smoke")
    bundle = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 32, 10, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=4)
    val = lm_units(make_lm_corpus(7, 8, 10, cfg.vocab_size), unit_size=4)
    return bundle, units, val


def _tc(**kw):
    base = dict(lr=0.5, optimizer="sgd", epochs=6, seed=0,
                nonfinite_guard=True,
                pgm=PGMConfig(subset_fraction=0.75, n_partitions=2,
                              select_every=2, warm_start_epochs=2))
    base.update(kw)
    return TrainConfig(**base)


def _run(lm, tc, fault_plan=None, *, ckpt_dir=None, resume=False,
         log_fn=None, epoch_chunk=2):
    bundle, units, val = lm
    return train_with_selection(
        bundle, units, tc, method="pgm", val_units=val, engine="scan",
        epoch_chunk=epoch_chunk, fault_plan=fault_plan, ckpt_dir=ckpt_dir,
        resume=resume, log_fn=log_fn or (lambda s: None))


def _bitwise_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# in-scan non-finite guard: exactness + retrace-freedom (engine level)
# ---------------------------------------------------------------------------

def test_guard_on_finite_data_is_bitwise_and_never_retraces(lm):
    """Guard-on over all-finite data must be bitwise identical to
    guard-off (the gate selects the new state everywhere), and a
    poisoned epoch must reuse the same executable — non-finiteness is
    traced data, not a trace constant."""
    bundle, units, _ = lm
    opt_init, _ = make_update_for(_tc())
    runs = {}
    for guard in (False, True):
        tc = _tc(nonfinite_guard=guard)
        eng = EpochEngine(bundle, tc, units, batch_units=2)
        p = bundle.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        p, o, losses = eng.run_epoch(p, o, tc.lr, eng.full_plan(0))
        runs[guard] = (p, o, losses, eng)
    for a, b in zip(runs[False][:3], runs[True][:3]):
        assert _bitwise_equal(a, b)
    eng = runs[True][3]
    assert int(eng.last_n_skipped) == 0
    # poisoned epoch on the SAME engine: one step skipped, no retrace —
    # non-finiteness is traced data, so the warm executable must serve it
    idx, w = eng.full_plan(1)
    w = np.array(w, np.float32)
    w[1] = np.nan
    w = jnp.asarray(w)
    with assert_retrace_free("guarded epoch on a poisoned plan"):
        p, o, losses = eng.run_epoch(*runs[True][:2], _tc().lr, (idx, w))
    assert int(eng.last_n_skipped) == 1
    assert np.asarray(eng.last_skipped).tolist() == [0.0, 1.0, 0.0, 0.0]
    assert float(losses[1]) == 0.0          # skipped step reports 0
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(p))


def test_skipped_step_equals_padding_row_bitwise(lm):
    """The documented skip semantics: a guarded-off NaN step leaves the
    carry (params, opt state — step counter included) bit-identical to
    running the same plan with that row as padding."""
    bundle, units, _ = lm
    tc = _tc()
    eng = EpochEngine(bundle, tc, units, batch_units=2)
    opt_init, _ = make_update_for(tc)
    idx, w = (np.asarray(eng.full_plan(0)[0]),
              np.asarray(eng.full_plan(0)[1], np.float32))
    poisoned_w = w.copy()
    poisoned_w[2] = np.nan
    padded_idx, padded_w = idx.copy(), w.copy()
    padded_idx[2], padded_w[2] = -1, 0.0
    outs = []
    for pi, pw in ((idx, poisoned_w), (padded_idx, padded_w)):
        p = bundle.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        outs.append(eng.run_epoch(p, o, tc.lr,
                                  (jnp.asarray(pi), jnp.asarray(pw)))[:2])
    assert _bitwise_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# end-to-end fault runs (loop level)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_nan_and_inf_step_faults_recover_exactly(lm):
    """A NaN (and an Inf) batch mid-run is skipped once and the run
    completes on the trajectory of its fault-free reference — the run
    that trained the same schedule without that batch — with the final
    val loss matching to well under 1e-3 (it is bitwise equal)."""
    tc = _tc()
    h_ref = _run(lm, tc, faults.FaultPlan(drop_step=(1, 2)))
    h_nan = _run(lm, tc, faults.FaultPlan(nan_step=(1, 2)))
    h_inf = _run(lm, tc, faults.FaultPlan(inf_step=(1, 2)))
    for h in (h_nan, h_inf):
        assert len(h.val_loss) == tc.epochs       # the run completed
        assert h.skipped_steps == 1
        assert h.rollbacks == 0
        assert np.isfinite(h.val_loss).all()
        assert abs(h.val_loss[-1] - h_ref.val_loss[-1]) < 1e-3
        assert _bitwise_equal(h.final_params, h_ref.final_params)
    assert h_ref.skipped_steps == 0               # reference ran fault-free


@pytest.mark.slow
def test_nan_epoch_trips_watchdog_rollback(lm, tmp_path):
    """An epoch of consecutive skips >= max_skipped_steps rolls back to
    the last good checkpoint with a re-keyed plan; the fire-once fault
    is gone on replay, so the run finishes finite with one rollback."""
    tc = _tc(epochs=4, max_skipped_steps=4)
    logs = []
    h = _run(lm, tc, faults.FaultPlan(nan_epoch=2),
             ckpt_dir=str(tmp_path / "ck"), log_fn=logs.append,
             epoch_chunk=1)
    assert h.rollbacks == 1
    assert h.skipped_steps >= tc.max_skipped_steps
    assert any("watchdog" in l and "rolling back" in l for l in logs)
    assert any("rolled back to epoch" in l for l in logs)
    assert len(h.val_loss) == tc.epochs
    assert np.isfinite(h.val_loss).all()
    assert np.isfinite(h.train_loss).all()


@pytest.mark.slow
def test_corrupt_checkpoint_falls_back_to_previous_intact(lm, tmp_path):
    """Byte-flipping the newest checkpoint must degrade resume to the
    previous intact step — and from there the rebuilt plans reproduce
    the uninterrupted run's tail exactly."""
    tc = _tc(epochs=4)
    d = str(tmp_path / "ck")
    h_full = _run(lm, tc, ckpt_dir=d, epoch_chunk=1)
    latest = ckpt_mod.latest_step(d)
    faults.corrupt_checkpoint(d)
    logs = []
    _, manifest = ckpt_mod.restore_latest_intact(d, log_fn=logs.append)
    assert manifest["step"] < latest
    assert any(f"step_{latest} unusable" in l for l in logs)
    # resume re-runs the epochs after the intact step on the same plans
    h_res = _run(lm, tc, ckpt_dir=d, resume=True, epoch_chunk=1)
    start = manifest["step"] + 1
    assert h_res.val_loss == h_full.val_loss[start:]
    assert h_res.train_loss == h_full.train_loss[start:]


def test_tampered_arrays_reports_every_corrupted_key(tmp_path):
    """A checksum failure must name ALL corrupted arrays, not die on the
    first — the operator needs the blast radius in one message."""
    d = str(tmp_path / "ck")
    tree = {"a": np.arange(6, dtype=np.float32),
            "b": np.ones((2, 3), np.float32),
            "c": np.zeros(4, np.int32)}
    ckpt_mod.save(d, 0, tree)
    targets = faults.tamper_arrays(d, keys=["['a']", "['c']"])
    with pytest.raises(IOError, match="2 array"):
        ckpt_mod.restore(d)
    try:
        ckpt_mod.restore(d)
    except IOError as e:
        for k in targets:
            assert k in str(e), (k, str(e))
    # verify=False still loads (escape hatch), intact keys are usable
    arrays, _ = ckpt_mod.restore(d, verify=False)
    assert np.array_equal(arrays["['b']"], tree["b"])


@pytest.mark.slow
def test_preemption_writes_resumable_checkpoint(lm, tmp_path):
    """SIGTERM finishes the in-flight chunk, writes an emergency
    checkpoint with a ``preempted`` manifest marker and exits; resuming
    continues on the uninterrupted run's exact trajectory."""
    tc = _tc()
    d = str(tmp_path / "ck")
    h_full = _run(lm, tc)
    logs = []
    h_cut = _run(lm, tc, faults.FaultPlan(preempt_after_epoch=1),
                 ckpt_dir=d, log_fn=logs.append)
    assert h_cut.preempted
    assert len(h_cut.val_loss) < tc.epochs
    assert any("emergency checkpoint" in l for l in logs)
    manifest = ckpt_mod.read_manifest(d)
    assert manifest["extra"].get("preempted") is True
    h_res = _run(lm, tc, ckpt_dir=d, resume=True)
    start = manifest["extra"]["epoch"] + 1
    assert h_cut.val_loss + h_res.val_loss == h_full.val_loss
    assert h_res.val_loss == h_full.val_loss[start:]


@pytest.mark.slow
def test_prefetch_worker_crash_is_transparent(lm):
    """A transient plan-builder failure is retried in place; because
    builders are pure, the recovered run is bit-identical to the
    fault-free one."""
    tc = _tc()
    h_clean = _run(lm, tc)
    fp = faults.FaultPlan(prefetch_fail_epochs=(1, 3))
    h_fault = _run(lm, tc, fp)
    assert ("prefetch", 1) in fp._fired and ("prefetch", 3) in fp._fired
    assert h_fault.train_loss == h_clean.train_loss
    assert h_fault.val_loss == h_clean.val_loss
    assert h_fault.skipped_steps == 0


# ---------------------------------------------------------------------------
# selection degradation ladder (pallas -> xla -> soft-random)
# ---------------------------------------------------------------------------

def _selector_setup(lm, **pgm_kw):
    bundle, units, _ = lm
    pc = dataclasses.replace(_tc().pgm, **pgm_kw)
    proj = make_proj_for(bundle, jax.random.PRNGKey(17),
                         pc.sketch_dim_h, pc.sketch_dim_v)
    units_dev = {k: jnp.asarray(v) for k, v in units.items()}
    params = bundle.init_params(jax.random.PRNGKey(0))
    return bundle, pc, proj, units_dev, params


def test_kernel_failure_falls_back_to_bit_identical_xla(lm):
    """A failing Pallas selection round warns once, re-jits stage A on
    the XLA path and returns exactly what a pure-XLA selector returns."""
    bundle, pc, proj, units_dev, params = _selector_setup(
        lm, kernel_impl="pallas")
    ref = ResidentSelector(
        bundle, dataclasses.replace(pc, kernel_impl="xla"), proj
    )(params, units_dev)
    logs = []
    with faults.failing_selection_kernels(("pallas",)):
        rs = ResidentSelector(bundle, pc, proj, on_failure="soft_random",
                              log_fn=logs.append)
        sel = rs(params, units_dev)
        sel2 = rs(params, units_dev)      # later rounds stay on XLA
    assert rs.kernel_impl == "xla"
    assert rs.degraded_rounds == 0        # fallback is NOT degradation
    assert np.array_equal(np.asarray(sel.indices), np.asarray(ref.indices))
    assert np.allclose(np.asarray(sel.weights), np.asarray(ref.weights))
    assert np.array_equal(np.asarray(sel2.indices),
                          np.asarray(ref.indices))
    assert sum("falling back" in l for l in logs) == 1   # warn-once


def test_total_scorer_failure_degrades_to_soft_random(lm):
    """Both kernel backends failing degrades the round to a soft-random
    subset of the right budget (training continues) and counts it; the
    fail-fast policy raises instead."""
    bundle, pc, proj, units_dev, params = _selector_setup(
        lm, kernel_impl="pallas")
    n_units = units_dev["tokens"].shape[0]
    budget = max(int(pc.subset_fraction * n_units), 1)
    logs = []
    with faults.failing_selection_kernels(("all",)):
        rs = ResidentSelector(bundle, pc, proj, on_failure="soft_random",
                              log_fn=logs.append)
        sel = rs(params, units_dev)
    assert rs.degraded_rounds == 1
    assert int(sel.n_selected) == budget
    idx = np.asarray(sel.indices)
    live = idx[idx >= 0]
    assert len(set(live.tolist())) == budget        # distinct real units
    assert np.allclose(np.asarray(sel.weights)[idx >= 0], 1.0)
    assert any("soft-random" in l for l in logs)
    with faults.failing_selection_kernels(("all",)):
        rs2 = ResidentSelector(bundle, pc, proj)      # default: raise
        with pytest.raises(RuntimeError, match="injected kernel failure"):
            rs2(params, units_dev)


def test_selector_fails_fast_by_default(lm):
    """Without the opt-in ladder a refused Pallas round raises: no silent
    retry on XLA, no degraded round."""
    bundle, pc, proj, units_dev, params = _selector_setup(
        lm, kernel_impl="pallas")
    with faults.failing_selection_kernels(("pallas",)):
        rs3 = ResidentSelector(bundle, pc, proj)
        with pytest.raises(RuntimeError, match="injected kernel failure"):
            rs3(params, units_dev)
    assert rs3.kernel_impl == "pallas" and rs3.degraded_rounds == 0


@pytest.mark.slow
def test_training_survives_total_scorer_failure(lm):
    """End-to-end: resident selection with every backend failing still
    trains to a finite final loss on the soft-random baseline."""
    bundle, units, val = lm
    tc = _tc(epochs=4)
    tc = dataclasses.replace(
        tc, pgm=dataclasses.replace(tc.pgm, on_failure="soft_random"))
    with faults.failing_selection_kernels(("all",)):
        h = train_with_selection(
            bundle, units, tc, method="pgm", val_units=val, engine="scan",
            resident_selection=True, log_fn=lambda s: None)
    assert len(h.val_loss) == tc.epochs
    assert np.isfinite(h.val_loss).all()
    assert h.selections                      # rounds still recorded
