"""Compressed pod-axis gradient collectives inside the sharded scanned
step (``train/engine.py`` two-level ``data x pod`` mode, DESIGN.md §5):

* fast tier — cheap in-process pieces on a degenerate (1,1)
  ``data x pod`` mesh (the whole vmap/pmean/error-feedback machinery
  runs, collectives are size-1): config validation, error-state shapes
  and donation, bit-exact no-op padding semantics for the
  error-feedback state, and the err sharding/restore spec rules;
* slow tier — full training runs: (1,1)-mesh parity vs the plain scan
  engine, graceful cross-compress-mode resume, and the 4-device
  subprocess suite (style of
  ``tests/test_sharded_engine.py``): ``compress_mode="none"`` is
  bit-close to both the single-device engine and the existing
  GSPMD-only ``data x model`` engine; top-k + error feedback trains the
  LM smoke to within 5% relative final val loss of dense; mid-run
  checkpoint resume with error-feedback state is bit-exact vs
  uninterrupted; and the lowered step reduces the pod collective at
  bf16 width while the compiled module carries pod-group all-reduces.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.contracts import assert_retrace_free
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import lm_units
from repro.data.synthetic import make_lm_corpus
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.train.engine import EpochEngine, make_engine
from repro.train.loop import train_with_selection
from repro.train.optim import make_update_for

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _lm_setup(n=16, seq=10, epochs=2, compress_mode="none", k_frac=0.1):
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=4)
    val = lm_units(make_lm_corpus(7, 8, seq, cfg.vocab_size), unit_size=4)
    tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=epochs,
                     compress_mode=compress_mode, compress_k_frac=k_frac,
                     pgm=PGMConfig())
    return m, units, val, tc


def _bitwise_equal(tree_a, tree_b):
    return all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(tree_a),
                               jax.tree.leaves(tree_b)))


# ---------------------------------------------------------------------------
# Degenerate (1,1) data x pod mesh: full machinery, single device (fast)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_pod_modes_match_plain_engine_on_1x1_mesh():
    m, units, val, tc = _lm_setup()
    h_plain = train_with_selection(m, units, tc, method="full",
                                   val_units=val, engine="scan")
    mesh = make_mesh((1, 1), ("data", "pod"))
    h_none = train_with_selection(
        m, units, dataclasses.replace(tc, compress_mode="none"),
        method="full", val_units=val, engine="scan", mesh=mesh)
    assert np.allclose(h_plain.train_loss, h_none.train_loss,
                       rtol=1e-3, atol=1e-3)
    assert np.allclose(h_plain.val_loss, h_none.val_loss,
                       rtol=1e-3, atol=1e-3)
    # (bf16 parity is covered by the 4-device slow suite, where the
    # collective is real)  topk still trains and carries residuals
    h_topk = train_with_selection(
        m, units, dataclasses.replace(tc, compress_mode="topk"),
        method="full", val_units=val, engine="scan", mesh=mesh)
    assert np.isfinite(h_topk.train_loss).all()


def test_topk_engine_error_state_shape_and_donation():
    m, units, _, tc = _lm_setup(compress_mode="topk")
    mesh = make_mesh((1, 1), ("data", "pod"))
    eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
    assert eng.uses_error_feedback and eng.n_pods == 1
    opt_init, _ = make_update_for(tc)
    p = m.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    p, o = eng.shard_state(p, o)
    p, o, losses = eng.run_epoch(p, o, tc.lr, eng.full_plan(0))
    err = eng.compress_state
    assert err is not None
    for pl, el in zip(jax.tree.leaves(p), jax.tree.leaves(err)):
        assert el.shape == (1,) + pl.shape
        assert el.dtype == jnp.float32
    # residuals are live after a top-k epoch
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(err))


def test_padding_steps_leave_error_state_bitwise():
    """An all-padding plan must advance nothing: params, opt state AND
    the error-feedback residuals come back bit-identical."""
    m, units, _, tc = _lm_setup(compress_mode="topk")
    mesh = make_mesh((1, 1), ("data", "pod"))
    eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
    opt_init, _ = make_update_for(tc)
    p = m.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    p, o = eng.shard_state(p, o)
    p, o, _ = eng.run_epoch(p, o, tc.lr, eng.full_plan(0))
    before = (jax.tree.map(np.asarray, p), jax.tree.map(np.asarray, o),
              jax.tree.map(np.asarray, eng.compress_state))
    pad_plan = (jnp.full((2, 2), -1, jnp.int32),
                jnp.zeros((2, 2), jnp.float32))
    p, o, losses = eng.run_epoch(p, o, tc.lr, pad_plan)
    assert np.asarray(losses).tolist() == [0.0, 0.0]
    after = (p, o, eng.compress_state)
    for b, a in zip(before, after):
        assert _bitwise_equal(b, a)


def test_recurrent_padding_on_pod_mesh_bitwise():
    """The weight-0 gate must hold through the pod-mode step on the
    recurrent substrate too (DESIGN.md §8): an all-padding plan on a
    (1,1) ``data x pod`` top-k engine leaves RWKV6 params, opt state and
    the error-feedback residuals bit-identical."""
    cfg = get_config("rwkv6-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 8, 10, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=2)
    tc = TrainConfig(lr=0.2, optimizer="sgd", epochs=1,
                     compress_mode="topk", compress_k_frac=0.1,
                     pgm=PGMConfig())
    mesh = make_mesh((1, 1), ("data", "pod"))
    eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
    opt_init, _ = make_update_for(tc)
    p = m.init_params(jax.random.PRNGKey(0))
    o = opt_init(p)
    p, o = eng.shard_state(p, o)
    p, o, _ = eng.run_epoch(p, o, tc.lr, eng.full_plan(0))
    before = (jax.tree.map(np.asarray, p), jax.tree.map(np.asarray, o),
              jax.tree.map(np.asarray, eng.compress_state))
    pad_plan = (jnp.full((2, 2), -1, jnp.int32),
                jnp.zeros((2, 2), jnp.float32))
    p, o, losses = eng.run_epoch(p, o, tc.lr, pad_plan)
    assert np.asarray(losses).tolist() == [0.0, 0.0]
    for b, a in zip(before, (p, o, eng.compress_state)):
        assert _bitwise_equal(b, a)


def test_guard_composes_with_pod_compression_bitwise():
    """The non-finite guard under pod-mode top-k compression
    (DESIGN.md §10): guard-on over finite data is bit-identical to
    guard-off — params, opt state AND error-feedback residuals — and a
    fully poisoned plan rolls all three back bit-exactly (the residuals
    gate through the same ``gate_step`` select as the padding rows)."""
    outs = {}
    for guard in (False, True):
        m, units, _, tc = _lm_setup(compress_mode="topk")
        tc = dataclasses.replace(tc, nonfinite_guard=guard)
        mesh = make_mesh((1, 1), ("data", "pod"))
        eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
        opt_init, _ = make_update_for(tc)
        p = m.init_params(jax.random.PRNGKey(0))
        o = opt_init(p)
        p, o = eng.shard_state(p, o)
        p, o, _ = eng.run_epoch(p, o, tc.lr, eng.full_plan(0))
        outs[guard] = (p, o, eng.compress_state, eng)
    for a, b in zip(outs[False][:3], outs[True][:3]):
        assert _bitwise_equal(a, b)
    # a poisoned epoch on the guarded engine: every step gated off,
    # residuals included — and no retrace
    p, o, err, eng = outs[True]
    before = (jax.tree.map(np.asarray, p), jax.tree.map(np.asarray, o),
              jax.tree.map(np.asarray, err))
    idx, w = eng.full_plan(1)
    w = jnp.full_like(w, jnp.nan)
    with assert_retrace_free("guarded compressed epoch on poisoned plan"):
        p, o, losses = eng.run_epoch(p, o, 0.5, (idx, w))
    assert int(eng.last_n_skipped) == int(idx.shape[0])
    assert np.asarray(losses).tolist() == [0.0] * int(idx.shape[0])
    for b, a in zip(before, (p, o, eng.compress_state)):
        assert _bitwise_equal(b, a)


def test_compress_config_validation():
    m, units, _, tc = _lm_setup(compress_mode="bf16")
    # compression without a pod axis on the mesh is a config error …
    with pytest.raises(ValueError, match="pod"):
        EpochEngine(m, tc, units, batch_units=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="pod"):
        EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
    # … and the host loop refuses it loudly instead of training dense
    # under a label that says compressed
    with pytest.raises(ValueError, match="scan"):
        make_engine("host", m, tc, units, batch_units=2)
    host = make_engine(
        "host", m, dataclasses.replace(tc, compress_mode="none"), units,
        batch_units=2)
    assert host.uses_error_feedback is False and host.compress_state is None


@pytest.mark.slow
def test_resume_across_compress_modes_is_graceful(tmp_path):
    """A topk resume from a checkpoint written without error-feedback
    state (different compress_mode) must warn and start residuals from
    zero — not KeyError on the missing 'err' arrays — and the reverse
    direction must warn about the mode switch."""
    m, units, val, tc = _lm_setup(epochs=2)
    mesh = make_mesh((1, 1), ("data", "pod"))
    d = str(tmp_path / "ck")
    train_with_selection(
        m, units, dataclasses.replace(tc, compress_mode="none"),
        method="full", val_units=val, engine="scan", mesh=mesh, ckpt_dir=d)
    logs = []
    h = train_with_selection(
        m, units, dataclasses.replace(tc, compress_mode="topk", epochs=3),
        method="full", val_units=val, engine="scan", mesh=mesh,
        ckpt_dir=d, resume=True, log_fn=logs.append)
    assert np.isfinite(h.train_loss).all()
    assert any("compress_mode" in l for l in logs)
    assert any("residuals restart from zero" in l for l in logs)
    # reverse: dense resume from a topk checkpoint ignores the err
    # arrays but flags the switch
    logs2 = []
    h2 = train_with_selection(
        m, units, dataclasses.replace(tc, compress_mode="none", epochs=4),
        method="full", val_units=val, engine="scan", mesh=mesh,
        ckpt_dir=d, resume=True, log_fn=logs2.append)
    assert np.isfinite(h2.train_loss).all()
    assert any("compress_mode" in l for l in logs2)


def test_err_sharding_and_restore_specs():
    m, units, _, tc = _lm_setup(compress_mode="topk")
    mesh = make_mesh((1, 1), ("data", "pod"))
    eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
    p = m.init_params(jax.random.PRNGKey(0))
    err = eng.init_compress_state(p)
    shs = eng.err_shardings(err)
    for sh in jax.tree.leaves(shs):
        assert sh.spec[0] == "pod"        # leading pod dim, always
    # checkpoint-tree paths: err leaves reshard with the pod-leading
    # spec, params/opt leaves with the plain param spec
    w = np.zeros((1, 64, 64), np.float32)
    sh = eng.restore_sharding("['err']['blocks']['attn']['wq']", w)
    assert sh.spec[0] == "pod"
    sh_p = eng.restore_sharding("['params']['blocks']['attn']['wq']",
                                w[0])
    assert sh_p.spec[0] != "pod"


# ---------------------------------------------------------------------------
# 4-device subprocess parity / convergence / resume (slow tier)
# ---------------------------------------------------------------------------

def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout


_SETUP = """
import dataclasses
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
pod_mesh = make_mesh((2, 2), ("data", "pod"))
"""


@pytest.mark.slow
def test_pod_none_matches_gspmd_only_engine():
    """The restructured step (per-pod grads + explicit fp32 pod pmean)
    must stay on the trajectory of both the single-device scan engine
    and the existing GSPMD-only data x model engine — same tolerance
    family as tests/test_sharded_engine.py."""
    out = _run(_SETUP + textwrap.dedent("""
        tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=4,
                         pgm=PGMConfig(subset_fraction=0.5, n_partitions=2,
                                       select_every=2, warm_start_epochs=1,
                                       sketch_dim_h=24, sketch_dim_v=24))
        h1 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan",
                                  batch_units=2)
        gspmd = make_mesh((2, 2), ("data", "model"))
        h2 = train_with_selection(m, units, tc, method="pgm",
                                  val_units=val, engine="scan",
                                  mesh=gspmd, batch_units=2)
        tcn = dataclasses.replace(tc, compress_mode="none")
        h3 = train_with_selection(m, units, tcn, method="pgm",
                                  val_units=val, engine="scan",
                                  mesh=pod_mesh, batch_units=2)
        for name, ref in (("single", h1), ("gspmd", h2)):
            assert np.allclose(ref.train_loss, h3.train_loss,
                               rtol=1e-3, atol=1e-3), \\
                (name, ref.train_loss, h3.train_loss)
            assert np.allclose(ref.val_loss, h3.val_loss,
                               rtol=1e-3, atol=1e-3), (name,)
            for sa, sb in zip(ref.selections, h3.selections):
                assert sa["indices"] == sb["indices"], (name, sa, sb)
        # chunked pod dispatch stays on the same trajectory
        h4 = train_with_selection(m, units, tcn, method="pgm",
                                  val_units=val, engine="scan",
                                  mesh=pod_mesh, batch_units=2,
                                  epoch_chunk=4)
        assert np.allclose(h3.train_loss, h4.train_loss, atol=1e-3)
        print("POD-NONE-OK")
    """))
    assert "POD-NONE-OK" in out


@pytest.mark.slow
def test_pod_topk_trains_within_5pct_of_dense():
    """Top-k (10% of entries per leaf) + error feedback must reach a
    final training loss within 5% relative of the dense pod run on the
    LM smoke — the convergence-preservation claim of Stich et al. is a
    claim about the optimized objective.  (This 32-sequence corpus
    overfits from epoch 5 on: validation loss rises while training loss
    falls, so the final validation loss measures overfitting noise, not
    convergence.)"""
    out = _run(_SETUP + textwrap.dedent("""
        base = TrainConfig(lr=0.3, optimizer="sgd", epochs=8,
                           pgm=PGMConfig())
        finals = {}
        for mode in ("none", "bf16", "topk"):
            tc = dataclasses.replace(base, compress_mode=mode,
                                     compress_k_frac=0.1)
            h = train_with_selection(m, units, tc, method="full",
                                     val_units=val, engine="scan",
                                     mesh=pod_mesh, batch_units=2)
            finals[mode] = h.train_loss[-1]
        rel_topk = abs(finals["topk"] - finals["none"]) / finals["none"]
        rel_bf16 = abs(finals["bf16"] - finals["none"]) / finals["none"]
        assert rel_topk <= 0.05, (finals, rel_topk)
        assert rel_bf16 <= 0.05, (finals, rel_bf16)
        print(f"POD-TOPK-OK rel_topk={rel_topk:.4f} rel_bf16={rel_bf16:.4f}")
    """))
    assert "POD-TOPK-OK" in out


@pytest.mark.slow
def test_pod_topk_resume_bit_exact():
    """Interrupt a chunked top-k run mid-way and resume: because the
    per-pod error-feedback residuals are checkpointed and restored, the
    remaining epochs are bit-identical to the uninterrupted run."""
    out = _run(_SETUP + textwrap.dedent("""
        import tempfile
        tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=6,
                         compress_mode="topk", compress_k_frac=0.1,
                         pgm=PGMConfig(subset_fraction=0.5, n_partitions=2,
                                       select_every=2, warm_start_epochs=1,
                                       sketch_dim_h=24, sketch_dim_v=24))
        with tempfile.TemporaryDirectory() as d:
            h_full = train_with_selection(
                m, units, tc, method="pgm", val_units=val, engine="scan",
                mesh=pod_mesh, batch_units=2, epoch_chunk=2,
                ckpt_dir=d + "/full")
            tc4 = dataclasses.replace(tc, epochs=4)
            train_with_selection(
                m, units, tc4, method="pgm", val_units=val, engine="scan",
                mesh=pod_mesh, batch_units=2, epoch_chunk=2,
                ckpt_dir=d + "/cut")
            h_res = train_with_selection(
                m, units, tc, method="pgm", val_units=val, engine="scan",
                mesh=pod_mesh, batch_units=2, epoch_chunk=2,
                ckpt_dir=d + "/cut", resume=True)
            import json, os
            man = json.load(open(os.path.join(
                d, "full", "step_5", "manifest.json")))
            assert man["compress_mode"] == "topk", man["compress_mode"]
            assert any("'err'" in k for k in man["arrays"]), \\
                list(man["arrays"])[:3]
        assert h_res.train_loss == h_full.train_loss[4:], \\
            (h_res.train_loss, h_full.train_loss)
        assert h_res.val_loss == h_full.val_loss[4:]
        print("POD-RESUME-OK")
    """))
    assert "POD-RESUME-OK" in out


@pytest.mark.slow
def test_pod_step_hlo_collective_and_divisibility():
    """The pod step's compiled artifacts satisfy the level-2 contracts
    (repro.analysis.contracts): in bf16 mode the lowered module reduces
    the gradient leaves at bf16 width — one reduce per param leaf, wire
    width checked pre-optimization — the compiled module's all-reduces
    group over the pod axis on both a 2x2 (data, pod) mesh (pairs
    {0,2},{1,3}) and a 1x4 all-pod mesh ({0,1,2,3}), the donated carry
    is marked donor, and the epoch body stays device-resident.
    Indivisible per-pod batches are a build-time error."""
    out = _run(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.analysis.contracts import (
            assert_collective_width, assert_donated,
            assert_no_host_transfers, assert_replica_groups)
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for
        cfg = get_config("starcoder2-3b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size), 4)
        tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=1,
                         compress_mode="bf16", pgm=PGMConfig())
        for shape in ((2, 2), (1, 4)):
            mesh = make_mesh(shape, ("data", "pod"))
            eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
            opt_init, _ = make_update_for(tc)
            p = m.init_params(jax.random.PRNGKey(0))
            o = opt_init(p)
            p, o = eng.shard_state(p, o)
            low = eng.lower_epoch(p, o, 0.5, eng.full_plan(0))
            n_leaves = len(jax.tree.leaves(p))
            # wire width: one bf16 pod reduce per gradient leaf, read
            # off the lowered module (XLA:CPU float-normalization
            # promotes compiled reduces, so compiled text can't prove
            # this)
            assert_collective_width(low, dtype="bf16",
                                    n_expected=n_leaves)
            # the (params, opt_state) carry is donated into the scan
            assert_donated(low, (p, o))
            txt = low.compile().as_text()
            # real all-reduces grouped exactly over the pod axis
            assert_replica_groups(txt, mesh, "pod")
            # the whole epoch dispatch stays device-resident
            assert_no_host_transfers(low, txt)
        # unit_size=3 batches cannot split across 2 pods
        mesh = make_mesh((2, 2), ("data", "pod"))
        units_odd = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size), 3)
        try:
            EpochEngine(m, tc, units_odd, batch_units=1, mesh=mesh)
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "pod" in str(e)
        print("POD-HLO-OK")
    """))
    assert "POD-HLO-OK" in out
