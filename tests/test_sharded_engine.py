"""Mesh-native scanned training (train/engine.py + train/loop.py):

* fast tier — ``run_epochs`` chunking is bit-for-bit identical to
  per-epoch ``run_epoch`` dispatches (and to sequential one-epoch
  chunks when validation/newbob run on device), the chunked training
  loop matches the per-epoch loop, and ``PlanPrefetcher`` returns
  bit-identical plans to synchronous building (including across a
  simulated resume);
* slow tier — subprocess runs on a forced 4-device host platform
  (alongside ``tests/test_sharding.py``) proving the sharded scanned
  epoch is bit-close to the single-device engine on the LM and RNN-T
  smoke configs, and that the sharded + chunked path still compiles
  one epoch executable across selection rounds (asserted through the
  ``analysis.contracts`` retrace contract).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.analysis.contracts import assert_retrace_free
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import lm_units
from repro.data.plan_prefetch import PlanPrefetcher
from repro.data.synthetic import make_lm_corpus
from repro.models.api import build_model
from repro.train.engine import EpochEngine, HostEngine, make_engine
from repro.train.loop import train_with_selection
from repro.train.optim import make_update_for

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _lm_setup(n=32, seq=12, epochs=4, optimizer="sgd"):
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=4)
    val = lm_units(make_lm_corpus(7, 16, seq, cfg.vocab_size), unit_size=4)
    tc = TrainConfig(
        lr=0.5, optimizer=optimizer, epochs=epochs,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=24, sketch_dim_v=24))
    return m, units, val, tc


def _bitwise_equal(tree_a, tree_b):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(tree_a),
                               jax.tree.leaves(tree_b)))


# ---------------------------------------------------------------------------
# Chunked dispatch == per-epoch dispatch, bit for bit (fast tier)
# ---------------------------------------------------------------------------

def test_run_epochs_matches_run_epoch_bit_for_bit():
    """One run_epochs chunk must produce exactly the params/opt_state/
    losses of the equivalent sequence of run_epoch dispatches (same lr:
    no validation, so newbob never fires)."""
    m, units, _, tc = _lm_setup()
    opt_init, _ = make_update_for(tc)

    eng_a = EpochEngine(m, tc, units, batch_units=2)
    p_a = m.init_params(jax.random.PRNGKey(0))
    o_a = opt_init(p_a)
    losses_a = []
    for e in range(3):
        p_a, o_a, l = eng_a.run_epoch(p_a, o_a, tc.lr, eng_a.full_plan(e))
        losses_a.append(np.asarray(l))

    eng_b = EpochEngine(m, tc, units, batch_units=2)
    p_b = m.init_params(jax.random.PRNGKey(0))
    o_b = opt_init(p_b)
    plans = [eng_b.full_plan(e) for e in range(3)]
    p_b, o_b, losses_b, vls, lrs, lr_out, prev = eng_b.run_epochs(
        p_b, o_b, tc.lr, float("inf"), plans)

    assert _bitwise_equal((p_a, o_a), (p_b, o_b)), \
        "chunked scan diverged from per-epoch dispatches"
    for i, l in enumerate(losses_a):
        assert np.array_equal(l, np.asarray(losses_b)[i])
    # no validation set: val losses are NaN and lr never anneals
    assert np.isnan(np.asarray(vls)).all()
    assert np.asarray(lrs).tolist() == [tc.lr] * 3
    assert float(lr_out) == tc.lr
    # the whole chunk is one executable: a second chunk of same-shape
    # plans must dispatch with zero fresh XLA compilations
    plans2 = [eng_b.full_plan(e) for e in range(3, 6)]
    with assert_retrace_free("second run_epochs chunk"):
        eng_b.run_epochs(p_b, o_b, tc.lr, float("inf"), plans2)


def test_run_epochs_device_newbob_matches_sequential_chunks():
    """Validation + newbob inside the chunk must match running the same
    epochs as size-1 chunks (lr/prev_loss round-trip through the host
    between them) — chunking changes dispatch, not math."""
    m, units, val, tc = _lm_setup(optimizer="adamw")
    opt_init, _ = make_update_for(tc)

    eng_a = EpochEngine(m, tc, units, val_units=val, batch_units=2)
    p_a = m.init_params(jax.random.PRNGKey(0))
    o_a = opt_init(p_a)
    lr, prev = tc.lr, float("inf")
    seq_vls, seq_lrs = [], []
    for e in range(3):
        p_a, o_a, _, v, ls, lr, prev = eng_a.run_epochs(
            p_a, o_a, lr, prev, [eng_a.full_plan(e)])
        seq_vls.append(float(v[0]))
        seq_lrs.append(float(ls[0]))
        lr, prev = float(lr), float(prev)

    eng_b = EpochEngine(m, tc, units, val_units=val, batch_units=2)
    p_b = m.init_params(jax.random.PRNGKey(0))
    o_b = opt_init(p_b)
    p_b, o_b, _, vls, lrs, _, _ = eng_b.run_epochs(
        p_b, o_b, tc.lr, float("inf"),
        [eng_b.full_plan(e) for e in range(3)])

    assert np.asarray(vls).tolist() == pytest.approx(seq_vls, abs=0)
    assert np.asarray(lrs).tolist() == pytest.approx(seq_lrs, abs=0)
    assert _bitwise_equal((p_a, o_a), (p_b, o_b))
    # annealing must actually have fired at this smoke scale, or the
    # lr comparison above proves nothing
    assert seq_lrs[-1] < tc.lr


def test_chunked_loop_matches_per_epoch_loop():
    """train_with_selection(epoch_chunk=4) must reproduce the per-epoch
    loop: same selections, losses to engine tolerance (the chunked path
    runs newbob in fp32 on device, the per-epoch path in python)."""
    m, units, val, tc = _lm_setup()
    h1 = train_with_selection(m, units, tc, method="pgm", val_units=val,
                              engine="scan")
    h2 = train_with_selection(m, units, tc, method="pgm", val_units=val,
                              engine="scan", epoch_chunk=4)
    assert np.allclose(h1.train_loss, h2.train_loss, atol=1e-3)
    assert np.allclose(h1.val_loss, h2.val_loss, atol=1e-3)
    assert np.allclose(h1.lr, h2.lr, atol=1e-6)
    for sa, sb in zip(h1.selections, h2.selections):
        assert sa["indices"] == sb["indices"]
    assert h1.cost_units == pytest.approx(h2.cost_units)


# ---------------------------------------------------------------------------
# Plan prefetch (fast tier)
# ---------------------------------------------------------------------------

def test_plan_prefetch_is_deterministic_and_bounded():
    m, units, _, tc = _lm_setup()
    eng = EpochEngine(m, tc, units, batch_units=2)
    idx = np.arange(6, dtype=np.int32)
    w = np.linspace(0.5, 2.0, 6).astype(np.float32)

    pf = PlanPrefetcher(max_pending=2)
    assert pf.schedule(("full", 0), lambda: eng.full_plan(0))
    assert pf.schedule(("subset", 0, 1),
                       lambda: eng.subset_plan(idx, w, 1))
    # buffer full: a third schedule is refused, not queued unboundedly
    assert not pf.schedule(("full", 2), lambda: eng.full_plan(2))
    got_full = pf.get(("full", 0), lambda: eng.full_plan(0))
    got_sub = pf.get(("subset", 0, 1), lambda: eng.subset_plan(idx, w, 1))
    # unscheduled key falls back to the synchronous builder
    got_miss = pf.get(("full", 2), lambda: eng.full_plan(2))
    pf.close()
    assert pf.hits == 2 and pf.misses == 1

    for got, want in [(got_full, eng.full_plan(0)),
                      (got_sub, eng.subset_plan(idx, w, 1)),
                      (got_miss, eng.full_plan(2))]:
        assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))

    # closed prefetcher refuses work instead of leaking a thread
    assert not pf.schedule(("full", 3), lambda: eng.full_plan(3))

    # invalidate frees slots held by keys that will never be fetched
    # (re-keying on a selection round), and re-scheduling a pending key
    # is an idempotent success, not a refusal
    pf2 = PlanPrefetcher(max_pending=1)
    assert pf2.schedule(("subset", 0, 1), lambda: eng.full_plan(1))
    assert pf2.schedule(("subset", 0, 1), lambda: eng.full_plan(1))
    assert not pf2.schedule(("subset", 1, 1), lambda: eng.full_plan(1))
    pf2.invalidate()
    assert pf2.schedule(("subset", 1, 1), lambda: eng.full_plan(1))
    got = pf2.get(("subset", 1, 1), lambda: eng.full_plan(1))
    assert np.array_equal(np.asarray(got[0]),
                          np.asarray(eng.full_plan(1)[0]))
    pf2.close()


def test_plan_prefetch_deterministic_across_resume():
    """A resumed run starts with an empty prefetch buffer; because plan
    builders are pure functions of (seed, epoch, selection), the
    prefetched and freshly-built plans are bit-identical — proven
    end-to-end: prefetch on vs off, and interrupted+resumed vs
    uninterrupted, all produce the same history."""
    import tempfile

    m, units, val, tc = _lm_setup(epochs=6)
    h_on = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                engine="scan", epoch_chunk=2)
    h_off = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                 engine="scan", epoch_chunk=2,
                                 plan_prefetch=False)
    assert h_on.train_loss == h_off.train_loss
    assert h_on.val_loss == h_off.val_loss

    with tempfile.TemporaryDirectory() as d:
        tc4 = TrainConfig(lr=tc.lr, optimizer=tc.optimizer, epochs=4,
                          pgm=tc.pgm)
        train_with_selection(m, units, tc4, method="pgm", val_units=val,
                             engine="scan", epoch_chunk=2, ckpt_dir=d)
        h_res = train_with_selection(m, units, tc, method="pgm",
                                     val_units=val, engine="scan",
                                     epoch_chunk=2, ckpt_dir=d, resume=True)
    assert h_res.train_loss == h_on.train_loss[4:]
    assert h_res.val_loss == h_on.val_loss[4:]


# ---------------------------------------------------------------------------
# Unified engine interface (fast tier)
# ---------------------------------------------------------------------------

def test_make_engine_dispatch_and_host_interface():
    m, units, val, tc = _lm_setup()
    scan = make_engine("scan", m, tc, units, val_units=val, batch_units=2)
    host = make_engine("host", m, tc, units, val_units=val, batch_units=2)
    assert isinstance(scan, EpochEngine) and isinstance(host, HostEngine)
    with pytest.raises(ValueError):
        make_engine("nope", m, tc, units)
    # host plans are the unpadded views over the same schedules
    idx = np.arange(5, dtype=np.int32)
    w = np.ones(5, np.float32)
    hp = host.subset_plan(idx, w, epoch=0)
    sp = scan.subset_plan(idx, w, epoch=0)
    live = scan.plan_live_steps(sp)
    assert np.array_equal(np.asarray(sp[0])[live], hp[0])
    # cost semantics: host charges the paper-style selected fraction
    # (8 units), scan charges the bucketed steps it executes (2 of the
    # 4 full-data steps at batch_units=2)
    assert host.epoch_cost(hp, n_selected=5) == pytest.approx(5 / 8)
    assert sp[0].shape == (2, 2)
    assert scan.epoch_cost(sp) == pytest.approx(0.5)
    # shard_state/restore_sharding are identity/None without a mesh
    p = {"w": np.zeros((2, 2), np.float32)}
    rp, ro = scan.shard_state(p, p)
    assert rp is p and ro is p
    assert scan.restore_sharding(".w", p["w"]) is None
    assert host.restore_sharding(".w", p["w"]) is None


# ---------------------------------------------------------------------------
# Sharded parity (slow tier; forced 4-device subprocess like
# tests/test_sharding.py)
# ---------------------------------------------------------------------------

def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


@pytest.mark.slow
def test_sharded_epoch_matches_single_device_lm():
    """The mesh-native scanned epoch (FSDP/TP carry + data-sharded
    batches on a 2x2 mesh) must be bit-close to the single-device scan
    engine — same tolerance family as the host/scan parity tests; rtol
    covers cross-device reduction reordering at loss scale ~15."""
    out = _run(textwrap.dedent("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.loop import train_with_selection
        assert jax.device_count() == 4
        cfg = get_config("starcoder2-3b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 32, 12, cfg.vocab_size,
                                        hard_fraction=0.4), 4)
        val = lm_units(make_lm_corpus(7, 16, 12, cfg.vocab_size), 4)
        tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=4,
                         pgm=PGMConfig(subset_fraction=0.5, n_partitions=2,
                                       select_every=2, warm_start_epochs=1,
                                       sketch_dim_h=24, sketch_dim_v=24))
        h1 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan")
        mesh = make_mesh((2, 2), ("data", "model"))
        h2 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan", mesh=mesh)
        assert np.allclose(h1.train_loss, h2.train_loss,
                           rtol=1e-3, atol=1e-3), \\
            (h1.train_loss, h2.train_loss)
        assert np.allclose(h1.val_loss, h2.val_loss,
                           rtol=1e-3, atol=1e-3), (h1.val_loss, h2.val_loss)
        for sa, sb in zip(h1.selections, h2.selections):
            assert sa["indices"] == sb["indices"], (sa, sb)
        assert h1.cost_units == h2.cost_units
        # chunked + sharded stays on the same trajectory
        h3 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan", mesh=mesh,
                                  epoch_chunk=4)
        assert np.allclose(h2.train_loss, h3.train_loss, atol=1e-3)
        print("SHARDED-LM-OK")
    """))
    assert "SHARDED-LM-OK" in out


@pytest.mark.slow
def test_sharded_epoch_matches_single_device_rnnt():
    out = _run(textwrap.dedent("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import asr_units
        from repro.data.synthetic import make_asr_corpus
        from repro.models.api import build_model
        from repro.train.loop import train_with_selection
        cfg = get_config("rnnt-crdnn-smoke")
        m = build_model(cfg)
        r = cfg.rnnt
        units = asr_units(make_asr_corpus(0, 16, n_feats=r.n_feats,
                                          vocab_size=r.vocab_size,
                                          noise_fraction=0.2, snr_db=5.0), 4)
        val = asr_units(make_asr_corpus(5, 8, n_feats=r.n_feats,
                                        vocab_size=r.vocab_size), 4)
        tc = TrainConfig(lr=0.05, optimizer="adamw", epochs=3,
                         pgm=PGMConfig(subset_fraction=0.5, n_partitions=2,
                                       select_every=2, warm_start_epochs=1,
                                       sketch_dim_h=16, sketch_dim_v=16,
                                       val_matching=True))
        h1 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan")
        mesh = make_mesh((2, 2), ("data", "model"))
        h2 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan", mesh=mesh,
                                  epoch_chunk=2)
        assert np.allclose(h1.train_loss, h2.train_loss,
                           rtol=1e-3, atol=1e-3), \\
            (h1.train_loss, h2.train_loss)
        assert np.allclose(h1.val_loss, h2.val_loss, rtol=1e-3, atol=1e-3)
        for sa, sb in zip(h1.selections, h2.selections):
            assert sa["indices"] == sb["indices"]
        print("SHARDED-RNNT-OK")
    """))
    assert "SHARDED-RNNT-OK" in out


@pytest.mark.slow
def test_sharded_chunked_path_compiles_one_epoch_executable():
    """Retrace-freedom survives the mesh + chunking: selection rounds
    with different n_selected inside one padding bucket share one
    chunked executable (the full warm-start chunk has its own)."""
    out = _run(textwrap.dedent("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh
        from repro.analysis.contracts import assert_retrace_free
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for
        cfg = get_config("starcoder2-3b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 128, 12, cfg.vocab_size,
                                        hard_fraction=0.4), 4)
        tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=1,
                         pgm=PGMConfig())
        mesh = make_mesh((2, 2), ("data", "model"))
        eng = EpochEngine(m, tc, units, batch_units=1, mesh=mesh)
        assert eng.steps_per_epoch_max == 32 and eng.plan_granule == 4
        opt_init, _ = make_update_for(tc)
        p = m.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        p, o = eng.shard_state(p, o)
        # warm-start: a chunk of 2 full epochs
        p, o, *_ = eng.run_epochs(p, o, tc.lr, float("inf"),
                                  [eng.full_plan(0), eng.full_plan(1)])
        # 3 selection rounds, n_selected all in one bucket, chunks of 2;
        # round 1 compiles the bucket-shape executable, rounds 2-3 must
        # dispatch with zero fresh XLA compilations
        rounds = []
        for rnd, n_sel in enumerate((13, 14, 16)):
            idx = np.arange(n_sel, dtype=np.int32)
            w = np.linspace(0.5, 2.0, n_sel).astype(np.float32)
            plans = [eng.subset_plan(idx, w, epoch=2 * rnd + e)
                     for e in range(2)]
            assert plans[0][0].shape == (16, 1)
            rounds.append(plans)
        p, o, losses, *_ = eng.run_epochs(p, o, tc.lr, float("inf"),
                                          rounds[0])
        with assert_retrace_free("sharded chunked subset rounds"):
            for plans in rounds[1:]:
                p, o, losses, *_ = eng.run_epochs(p, o, tc.lr,
                                                  float("inf"), plans)
                assert np.isfinite(np.asarray(losses)).all()
        print("TRACES-OK")
    """))
    assert "TRACES-OK" in out
