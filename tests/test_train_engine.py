"""Scanned epoch engine (train/engine.py): parity against the legacy
host loop on both an LM-smoke and the RNN-T-smoke config, plus fast
micro-properties — batch-plan determinism across resume, weighted-batch
weight expansion, and donation not retaining stale buffers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import (
    asr_units,
    epoch_plan,
    lm_units,
    subset_epoch_plan,
    subset_iterator,
)
from repro.data.synthetic import make_asr_corpus, make_lm_corpus
from repro.models.api import build_model
from repro.train.engine import EpochEngine
from repro.train.loop import train_with_selection


def _lm_setup(n=32, seq=12, epochs=4):
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=4)
    val = lm_units(make_lm_corpus(7, 16, seq, cfg.vocab_size), unit_size=4)
    tc = TrainConfig(
        lr=0.5, optimizer="sgd", epochs=epochs,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=24, sketch_dim_v=24))
    return m, units, val, tc


def _rnnt_setup(n=16, epochs=3):
    cfg = get_config("rnnt-crdnn-smoke")
    m = build_model(cfg)
    r = cfg.rnnt
    units = asr_units(make_asr_corpus(0, n, n_feats=r.n_feats,
                                      vocab_size=r.vocab_size,
                                      noise_fraction=0.2), 4)
    val = asr_units(make_asr_corpus(5, 8, n_feats=r.n_feats,
                                    vocab_size=r.vocab_size), 4)
    tc = TrainConfig(
        lr=0.05, optimizer="adamw", epochs=epochs,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=16, sketch_dim_v=16,
                      val_matching=True))
    return m, units, val, tc


# ---------------------------------------------------------------------------
# Parity: identical seeds => the scanned engine reproduces the legacy
# host loop's per-epoch losses and selected indices
# ---------------------------------------------------------------------------

def _assert_history_parity(h_host, h_scan, atol):
    assert np.allclose(h_host.train_loss, h_scan.train_loss, atol=atol), \
        (h_host.train_loss, h_scan.train_loss)
    assert np.allclose(h_host.val_loss, h_scan.val_loss, atol=atol), \
        (h_host.val_loss, h_scan.val_loss)
    assert len(h_host.selections) == len(h_scan.selections)
    for sh, ss in zip(h_host.selections, h_scan.selections):
        assert sh["epoch"] == ss["epoch"]
        assert sh["indices"] == ss["indices"], (sh, ss)
        assert np.allclose(sh["weights"], ss["weights"], atol=atol)
    assert h_host.cost_units == pytest.approx(h_scan.cost_units)


def test_scan_engine_matches_host_loop_lm():
    m, units, val, tc = _lm_setup()
    h_host = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="host")
    h_scan = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="scan")
    _assert_history_parity(h_host, h_scan, atol=1e-3)


@pytest.mark.slow
def test_scan_engine_matches_host_loop_rnnt():
    m, units, val, tc = _rnnt_setup()
    h_host = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="host")
    h_scan = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="scan")
    _assert_history_parity(h_host, h_scan, atol=1e-3)


# ---------------------------------------------------------------------------
# Micro-properties (fast tier)
# ---------------------------------------------------------------------------

def test_epoch_plan_determinism_across_resume():
    """The (seed, epoch) keying makes the schedule a pure function — a
    resumed run rebuilds byte-identical plans for the remaining epochs."""
    for epoch in (0, 3):
        a = epoch_plan(12, seed=5, epoch=epoch, batch_units=2)
        b = epoch_plan(12, seed=5, epoch=epoch, batch_units=2)
        assert a.shape == (6, 2) and np.array_equal(a, b)
        assert sorted(a.reshape(-1).tolist()) == list(range(12))
    assert not np.array_equal(epoch_plan(12, 5, 0), epoch_plan(12, 5, 1))
    assert not np.array_equal(epoch_plan(12, 5, 0), epoch_plan(12, 6, 0))

    idx = np.asarray([3, 7, -1, 1, 5, -1], np.int32)
    w = np.asarray([1.0, 2.0, 0.0, 0.5, 1.5, 0.0], np.float32)
    pi1, pw1 = subset_epoch_plan(idx, w, seed=5, epoch=2, batch_units=2)
    pi2, pw2 = subset_epoch_plan(idx, w, seed=5, epoch=2, batch_units=2)
    assert np.array_equal(pi1, pi2) and np.array_equal(pw1, pw2)
    assert pi1.shape == (2, 2)                       # -1 dropped, 4//2 steps
    assert set(pi1.reshape(-1).tolist()) <= {3, 7, 1, 5}
    # weights travel with their indices through the shuffle
    by_idx = dict(zip(idx.tolist(), w.tolist()))
    for i, ww in zip(pi1.reshape(-1), pw1.reshape(-1)):
        assert by_idx[int(i)] == float(ww)


def test_subset_iterator_matches_plan():
    """The host iterator is a view over the same plan (order parity by
    construction)."""
    units = {"tokens": np.arange(48, dtype=np.int32).reshape(12, 4),
             "weights": np.ones((12, 4), np.float32)}
    idx = np.asarray([0, 2, 4, 6, 8, 10], np.int32)
    w = np.linspace(0.5, 3.0, 6).astype(np.float32)
    pi, pw = subset_epoch_plan(idx, w, seed=1, epoch=0, batch_units=2)
    batches = list(subset_iterator(units, idx, w, seed=1, epoch=0,
                                   batch_units=2))
    assert len(batches) == pi.shape[0]
    for (sel, ww), b in zip(zip(pi, pw), batches):
        assert np.array_equal(b["tokens"],
                              units["tokens"][sel].reshape(-1))
        assert np.allclose(b["weights"], np.repeat(ww, 4))


def test_weighted_batch_weights_reach_the_loss():
    """Per-unit OMP weights must scale the per-example loss weights inside
    the scanned batch exactly like the host iterator does."""
    m, units, _, tc = _lm_setup(n=16, epochs=1)
    eng = EpochEngine(m, tc, units, batch_units=2)
    idx = np.asarray([0, 1, 2, 3], np.int32)
    w = np.asarray([2.0, 0.5, 1.0, 3.0], np.float32)
    plan_idx, plan_w = eng.subset_plan(idx, w, epoch=0)
    # reconstruct the first scanned batch by hand
    sel, ww = np.asarray(plan_idx)[0], np.asarray(plan_w)[0]
    want = units["weights"][sel].reshape(-1) * np.repeat(ww, eng.unit_size)
    got = np.asarray(eng.units["weights"])[sel].reshape(-1) \
        * np.repeat(ww, eng.unit_size)
    assert np.allclose(got, want)
    # and a weight-2x selection changes the loss vs weight-1x
    params = m.init_params(jax.random.PRNGKey(0))
    opt0 = {"step": jnp.zeros((), jnp.int32)}
    p1, o1, losses_w = eng.run_epoch(params, opt0, 0.0,
                                     (plan_idx, plan_w))
    params2 = m.init_params(jax.random.PRNGKey(0))
    ones = jnp.ones_like(plan_w)
    p2, o2, losses_1 = eng.run_epoch(params2, {"step": jnp.zeros((), jnp.int32)},
                                     0.0, (plan_idx, ones))
    assert losses_w.shape == losses_1.shape == (2,)
    assert not np.allclose(np.asarray(losses_w), np.asarray(losses_1))


# ---------------------------------------------------------------------------
# Recurrent-state carries through the epoch scan (DESIGN.md §8): the
# RWKV6 / RecurrentGemma time recurrences zero-init per utterance, so
# the scan-of-scan must carry no hidden state across steps, resume
# bit-exact, and treat padding steps as bit-exact no-ops.
# ---------------------------------------------------------------------------

RECURRENT = ["rwkv6-3b",
             pytest.param("recurrentgemma-9b", marks=pytest.mark.slow)]


def _recurrent_setup(arch, n=16, seq=10, epochs=4):
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=2)
    val = lm_units(make_lm_corpus(7, 8, seq, cfg.vocab_size), unit_size=2)
    tc = TrainConfig(
        lr=0.2, optimizer="sgd", epochs=epochs,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=16, sketch_dim_v=16))
    return m, units, val, tc


@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_state_resets_per_utterance(arch):
    """The recurrence is per-utterance: an example's loss is identical
    whether it shares a batch with others or is evaluated alone, and
    repeating a step at lr=0 reproduces the loss bitwise — no recurrent
    state survives between utterances or between scan steps."""
    m, units, _, tc = _recurrent_setup(arch)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v[0]) for k, v in units.items()}
    pe = m.per_example_loss(params, batch)
    for i in range(int(pe.shape[0])):
        alone = m.per_example_loss(
            params, {k: v[i:i + 1] for k, v in batch.items()})
        assert np.allclose(np.asarray(alone[0]), np.asarray(pe[i]),
                           rtol=1e-5, atol=1e-6), (arch, i)
    # same unit scheduled twice in one scanned epoch at lr=0: both steps
    # see identical params AND identical (fresh) recurrent state
    eng = EpochEngine(m, tc, units, batch_units=2)
    plan = (jnp.zeros((2, 2), jnp.int32), jnp.ones((2, 2), jnp.float32))
    opt0 = {"step": jnp.zeros((), jnp.int32)}
    _, _, losses = eng.run_epoch(params, opt0, 0.0, plan)
    l = np.asarray(losses)
    assert l[0] == l[1], (arch, l)


@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_padding_steps_are_bitwise_noops(arch):
    """An all-padding plan (weight-0 gated steps) leaves params and opt
    state bit-identical on the recurrent substrates — the gate must hold
    through the scan-of-scan exactly as on dense LMs."""
    m, units, _, tc = _recurrent_setup(arch)
    eng = EpochEngine(m, tc, units, batch_units=2)
    from repro.train.optim import make_update_for
    opt_init, _ = make_update_for(tc)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = opt_init(params)
    params, opt, _ = eng.run_epoch(params, opt, tc.lr, eng.full_plan(0))
    before = (jax.tree.map(np.asarray, params), jax.tree.map(np.asarray, opt))
    pad_plan = (jnp.full((2, 2), -1, jnp.int32),
                jnp.zeros((2, 2), jnp.float32))
    params, opt, losses = eng.run_epoch(params, opt, tc.lr, pad_plan)
    assert np.asarray(losses).tolist() == [0.0, 0.0]
    for b, a in zip(before, (params, opt)):
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(b), jax.tree.leaves(a)))


@pytest.mark.parametrize("arch", RECURRENT)
def test_recurrent_resume_bit_exact(arch, tmp_path):
    """Interrupt a selection run mid-way and resume from checkpoint: the
    remaining epochs reproduce the uninterrupted run exactly — the
    recurrent substrates carry nothing outside (params, opt, plan
    state), so resume is bit-exact like the dense case."""
    m, units, val, tc = _recurrent_setup(arch, epochs=4)
    h_full = train_with_selection(
        m, units, tc, method="pgm", val_units=val, engine="scan",
        ckpt_dir=str(tmp_path / "full"))
    import dataclasses
    tc2 = dataclasses.replace(tc, epochs=2)
    train_with_selection(
        m, units, tc2, method="pgm", val_units=val, engine="scan",
        ckpt_dir=str(tmp_path / "cut"))
    h_res = train_with_selection(
        m, units, tc, method="pgm", val_units=val, engine="scan",
        ckpt_dir=str(tmp_path / "cut"), resume=True)
    assert h_res.train_loss == h_full.train_loss[2:], \
        (arch, h_res.train_loss, h_full.train_loss)
    assert h_res.val_loss == h_full.val_loss[2:]


def test_guard_composes_with_padding_gate_bitwise():
    """The non-finite guard's gate leaves the same state as a weight-0
    padding row that the scan skips (DESIGN.md §10): on a plan mixing
    real and padding rows, guard-on must be bit-identical to guard-off,
    padding rows must not count as skipped, and a poisoned real row must
    gate off exactly like a padding row."""
    import dataclasses
    m, units, _, tc = _lm_setup(n=16, epochs=1)
    from repro.train.optim import make_update_for
    opt_init, _ = make_update_for(tc)
    # subset plan with trailing padding (2 real units into 2-unit batches,
    # padded to 2 steps by construction below)
    idx = np.asarray([[0, 1], [-1, -1]], np.int32)
    w = np.asarray([[1.0, 1.0], [0.0, 0.0]], np.float32)
    outs = {}
    for guard in (False, True):
        eng = EpochEngine(m, dataclasses.replace(tc, nonfinite_guard=guard),
                          units, batch_units=2)
        p = m.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        outs[guard] = eng.run_epoch(p, o, tc.lr,
                                    (jnp.asarray(idx), jnp.asarray(w)))
        if guard:
            # padding is gated, not "skipped": the guard metric only
            # reports suppressed *live* steps
            assert int(eng.last_n_skipped) == 0
            assert np.asarray(eng.last_skipped).tolist() == [0.0, 0.0]
    for a, b in zip(outs[False], outs[True]):
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    # poisoned real row == padding row, bit for bit (carry incl. opt step)
    eng = EpochEngine(m, dataclasses.replace(tc, nonfinite_guard=True),
                      units, batch_units=2)
    w_nan = np.asarray([[np.nan, np.nan], [0.0, 0.0]], np.float32)
    p = m.init_params(jax.random.PRNGKey(0))
    p2, o2, losses = eng.run_epoch(p, opt_init(p), tc.lr,
                                   (jnp.asarray(idx), jnp.asarray(w_nan)))
    assert int(eng.last_n_skipped) == 1
    assert np.asarray(losses).tolist() == [0.0, 0.0]
    pad_only = (jnp.full((2, 2), -1, jnp.int32),
                jnp.zeros((2, 2), jnp.float32))
    p3 = m.init_params(jax.random.PRNGKey(0))
    p4, o4, _ = eng.run_epoch(p3, opt_init(p3), tc.lr, pad_only)
    for a, b in zip((p2, o2), (p4, o4)):
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# Padding rows run no step: an RNN-T smoke bundle whose loss bumps a host
# counter each time a step runs it
# ---------------------------------------------------------------------------

#: the live rows of the 8-step padded plan (padding before, between and
#: after them)
PADDED_LIVE_ROWS = [2, 5]


def _counting_rnnt(n_units=16):
    import dataclasses
    cfg = get_config("rnnt-crdnn-smoke")
    base = build_model(cfg)
    calls = [0]

    def bump():
        calls[0] += 1

    def loss_fn(params, batch, **kw):
        jax.debug.callback(bump)
        return base.loss_fn(params, batch, **kw)

    units = asr_units(make_asr_corpus(0, n_units, n_feats=cfg.rnnt.n_feats,
                                      vocab_size=cfg.rnnt.vocab_size), 1)
    return dataclasses.replace(base, loss_fn=loss_fn), units, calls


def _padded_against_unpadded(engine: str, guard: bool) -> dict:
    """Two live rows of 4 utterances, once padded to 8 steps with the live
    rows at ``PADDED_LIVE_ROWS`` and once unpadded, each from the same
    fresh state: the loss calls and ``epoch.gated_steps`` of each
    dispatch, and whether the two agree bit for bit.  ``engine`` is
    ``one_device``, ``pod`` (top-k compression with error feedback on a
    (1, 1) ``data x pod`` mesh) or ``data_mesh`` (4 devices)."""
    from repro import obs
    from repro.launch.mesh import make_mesh
    from repro.train.engine import Plan
    from repro.train.optim import make_update_for
    m, units, calls = _counting_rnnt()
    mesh = {"one_device": lambda: None,
            "pod": lambda: make_mesh((1, 1), ("data", "pod")),
            "data_mesh": lambda: make_mesh((4,), ("data",))}[engine]()
    tc = TrainConfig(lr=0.01, optimizer="adamw", nonfinite_guard=guard,
                     compress_mode="topk" if engine == "pod" else "none",
                     pgm=PGMConfig())
    eng = EpochEngine(m, tc, units, batch_units=4, mesh=mesh)
    opt_init, _ = make_update_for(tc)
    ids = np.arange(8, dtype=np.int32)
    w = np.linspace(0.5, 2.0, 8).astype(np.float32)
    trailing = eng.subset_plan(ids, w, 0, pad_to_steps=8)
    rest = [r for r in range(8) if r not in PADDED_LIVE_ROWS]
    inv = np.argsort(PADDED_LIVE_ROWS + rest)
    padded = Plan(trailing[0][inv], trailing[1][inv], trailing.counts)
    unpadded = eng.subset_plan(ids, w, 0, pad_to_steps=0)
    runs = []
    for plan in (padded, unpadded):
        p = m.init_params(jax.random.PRNGKey(0))
        p, o = eng.shard_state(p, opt_init(p))
        eng.compress_state = None
        calls[0], gated = 0, obs.value("epoch.gated_steps")
        p, o, losses = eng.run_epoch(p, o, tc.lr, plan)
        state = jax.tree.map(np.asarray, (p, o, eng.compress_state))
        jax.effects_barrier()
        runs.append(dict(
            calls=calls[0], gated=obs.value("epoch.gated_steps") - gated,
            losses=np.asarray(losses), state=state,
            skipped=None if not guard else
            np.asarray(eng.last_skipped).tolist()))
    live = eng.plan_live_steps(padded)
    return {"calls": [r["calls"] for r in runs],
            "gated": [r["gated"] for r in runs],
            "live_rows": np.flatnonzero(live).tolist(),
            "same_state": all(
                np.array_equal(a, b) for a, b in zip(
                    jax.tree.leaves(runs[0]["state"]),
                    jax.tree.leaves(runs[1]["state"]))),
            "same_live_losses": np.array_equal(runs[0]["losses"][live],
                                               runs[1]["losses"]),
            "padding_losses": runs[0]["losses"][~live].tolist(),
            "skipped": [r["skipped"] for r in runs]}


def _in_four_device_process(engine: str, guard: bool) -> dict:
    import json
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(here, "..", "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"import json, sys; sys.path.insert(0, {here!r}); "
            f"import test_train_engine as t; print(json.dumps("
            f"t._padded_against_unpadded({engine!r}, {guard!r})))")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("engine", ["one_device", "pod", "data_mesh"])
@pytest.mark.parametrize("guard", [False, True], ids=["no_guard", "guard"])
def test_padding_rows_run_no_step(engine, guard):
    """A padding row runs no step, wherever it sits in the plan: the loss
    runs once per live row, the padded plan ends bit for bit where the
    unpadded one does (the pod engine's error-feedback state included),
    and ``epoch.gated_steps`` counts the padding rows skipped."""
    got = (_in_four_device_process(engine, guard) if engine == "data_mesh"
           else _padded_against_unpadded(engine, guard))
    assert got["live_rows"] == PADDED_LIVE_ROWS
    assert got["calls"] == [2, 2]
    assert got["gated"] == [6, 0]
    assert got["same_state"] and got["same_live_losses"]
    assert got["padding_losses"] == [0.0] * 6
    if guard:
        # padding is skipped, not "skipped": the guard reports live steps
        assert got["skipped"] == [[0.0] * 8, [0.0] * 2]


def test_chunked_and_unpadded_epochs_count_their_steps():
    """The chunked dispatch skips padding rows like ``run_epoch``; a full
    plan and the host loop, whose plans are never padded, gate nothing."""
    from repro import obs
    from repro.train.engine import HostEngine
    from repro.train.optim import make_update_for
    m, units, calls = _counting_rnnt()
    tc = TrainConfig(lr=0.01, optimizer="adamw", pgm=PGMConfig())
    opt_init, _ = make_update_for(tc)
    ids = np.arange(8, dtype=np.int32)
    w = np.ones(8, np.float32)

    def calls_and_gated(run):
        p = m.init_params(jax.random.PRNGKey(0))
        calls[0], gated = 0, obs.value("epoch.gated_steps")
        run(p, opt_init(p))
        jax.effects_barrier()
        return calls[0], obs.value("epoch.gated_steps") - gated

    eng = EpochEngine(m, tc, units, batch_units=4)
    plans = [eng.subset_plan(ids, w, e, pad_to_steps=8) for e in (0, 1)]
    assert calls_and_gated(lambda p, o: eng.run_epochs(
        p, o, tc.lr, float("inf"), plans)) == (4, 12)
    assert calls_and_gated(lambda p, o: eng.run_epoch(
        p, o, tc.lr, eng.full_plan(0))) == (eng.steps_per_epoch_max, 0)
    host = HostEngine(m, tc, units, batch_units=4)
    assert calls_and_gated(lambda p, o: host.run_epoch(
        p, o, tc.lr, host.subset_plan(ids, w, 0))) == (2, 0)


def test_emergency_checkpoint_resume_bit_exact_mid_chunk(tmp_path):
    """A preemption landing mid-run on a chunked dispatch checkpoints at
    the chunk boundary and resumes bit-exactly onto the uninterrupted
    trajectory — the guard's skip counters and the chunked newbob state
    all travel through the manifest."""
    import dataclasses
    from repro.train import faults
    m, units, val, tc = _lm_setup(epochs=4)
    tc = dataclasses.replace(tc, nonfinite_guard=True)
    d = str(tmp_path / "ck")
    h_full = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan",
                                  epoch_chunk=2)
    # warm start is 1 epoch, so the chunks are [0], [1,2], [3]: a SIGTERM
    # requested after epoch 1 lands mid-chunk — epoch 2 still runs (the
    # in-flight dispatch completes) and the checkpoint is cut at epoch 2
    h_cut = train_with_selection(
        m, units, tc, method="pgm", val_units=val, engine="scan",
        epoch_chunk=2, ckpt_dir=d,
        fault_plan=faults.FaultPlan(preempt_after_epoch=1))
    assert h_cut.preempted and len(h_cut.val_loss) == 3
    h_res = train_with_selection(m, units, tc, method="pgm",
                                 val_units=val, engine="scan",
                                 epoch_chunk=2, ckpt_dir=d, resume=True)
    assert h_cut.val_loss + h_res.val_loss == h_full.val_loss
    assert h_cut.train_loss + h_res.train_loss == h_full.train_loss


def test_donation_does_not_retain_stale_buffers():
    """run_epoch donates (params, opt_state): the inputs' buffers are
    consumed (deleted when the backend supports donation) and the engine
    keeps working from the returned state — nothing stale is retained."""
    m, units, _, tc = _lm_setup(n=16, epochs=1)
    eng = EpochEngine(m, tc, units, batch_units=2)
    params = m.init_params(jax.random.PRNGKey(0))
    opt_state = {"step": jnp.zeros((), jnp.int32)}
    in_leaf = jax.tree.leaves(params)[0]
    p1, o1, l1 = eng.run_epoch(params, opt_state, tc.lr,
                               eng.full_plan(epoch=0))
    assert in_leaf.is_deleted(), "donated params buffer was retained"
    # chaining from the returned state works (nothing references the old
    # buffers), and the second epoch is a cache hit on the same executable
    p2, o2, l2 = eng.run_epoch(p1, o1, tc.lr, eng.full_plan(epoch=1))
    assert np.isfinite(np.asarray(l2)).all()
    assert int(o2["step"]) == 2 * l1.shape[0]