"""Resident selection rounds + retrace-free subset plans (DESIGN.md §1/§3):

* the epoch executable compiles exactly once across selection rounds with
  different ``n_selected`` (padded plans share one shape);
* weight-0 padding rows are bit-exact no-ops for ``(params, opt_state)``
  and contribute nothing to metrics;
* ``ResidentSelector`` stage A matches the host ``units_gradients`` path
  to fp32 tolerance on both the LM and RNN-T smoke configs, and the
  resulting selections agree;
* the end-to-end ``resident_selection=True`` training loop matches the
  host-selection scan loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.contracts import assert_retrace_free
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.core.lastlayer import make_proj_for, units_gradients
from repro.core.pgm import ResidentSelector, pgm_select
from repro.data.pipeline import lm_units, subset_epoch_plan, subset_iterator
from repro.data.synthetic import make_lm_corpus
from repro.models.api import build_model
from repro.train.engine import EpochEngine
from repro.train.loop import make_train_step, train_with_selection
from repro.train.optim import make_update_for


def _lm_engine(n_examples=64, seq=12, unit_size=4, batch_units=2,
               optimizer="adamw"):
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n_examples, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=unit_size)
    tc = TrainConfig(lr=0.5, optimizer=optimizer, epochs=1, pgm=PGMConfig())
    return m, units, tc, EpochEngine(m, tc, units, batch_units=batch_units)


def _stacked_units(m, n_units, B=2, S=16, seed0=0):
    return jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[m.make_batch(jax.random.PRNGKey(seed0 + i), B, S)
          for i in range(n_units)])


# ---------------------------------------------------------------------------
# Retrace-freedom
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_epoch_executable_compiles_once_across_rounds():
    """≥3 subset rounds with different n_selected inside one padding
    bucket must share one compiled epoch executable: after the first
    subset round compiles the bucket shape, the remaining rounds must
    dispatch with zero fresh XLA compilations (asserted through the
    shared ``analysis.contracts`` retrace contract, which counts real
    compiles — not a per-function side-effect counter)."""
    m, units, tc, eng = _lm_engine(n_examples=128, batch_units=1)
    assert eng.steps_per_epoch_max == 32 and eng.plan_granule == 4
    opt_init, _ = make_update_for(tc)
    params = m.init_params(jax.random.PRNGKey(0))
    opt = opt_init(params)
    params, opt, _ = eng.run_epoch(params, opt, tc.lr, eng.full_plan(0))
    rounds = []
    for rnd, n_sel in enumerate((13, 14, 16)):
        idx = np.arange(n_sel, dtype=np.int32)
        w = np.linspace(0.5, 2.0, n_sel).astype(np.float32)
        plan = eng.subset_plan(idx, w, epoch=rnd + 1)
        assert plan[0].shape == (16, 1)      # one bucket for all 3 rounds
        rounds.append((n_sel, plan))
    # round 1 compiles the bucket-shape executable; rounds 2-3 must not
    n_sel, plan = rounds[0]
    params, opt, losses = eng.run_epoch(params, opt, tc.lr, plan)
    with assert_retrace_free("subset rounds sharing a padding bucket"):
        for n_sel, plan in rounds[1:]:
            params, opt, losses = eng.run_epoch(params, opt, tc.lr, plan)
            assert int(eng.plan_live_steps(plan).sum()) == n_sel
            assert np.isfinite(np.asarray(losses)).all()


def test_subset_plan_padding_shape_and_sentinels():
    idx = np.asarray([3, 7, 1, 5], np.int32)
    w = np.asarray([1.0, 2.0, 0.5, 1.5], np.float32)
    pi, pw = subset_epoch_plan(idx, w, seed=0, epoch=0, batch_units=2,
                               pad_to_steps=5)
    assert pi.shape == pw.shape == (5, 2)
    assert (pi[2:] == -1).all() and (pw[2:] == 0).all()
    assert (pi[:2] >= 0).all()
    # padding never truncates real steps
    with pytest.raises(ValueError):
        subset_epoch_plan(idx, w, seed=0, epoch=0, batch_units=2,
                          pad_to_steps=1)
    # unpadded (legacy) shape is untouched
    pi0, _ = subset_epoch_plan(idx, w, seed=0, epoch=0, batch_units=2)
    assert pi0.shape == (2, 2)


def test_bucketed_padding_bounds_subset_epoch_cost():
    """Padding must not erase the subset-compute saving: the padded plan
    is shaped at most one granule (1/8 epoch) beyond the live steps, not
    the full-data step count, and its padding rows run no step, so the
    epoch is charged its live steps alone."""
    m, units, tc, eng = _lm_engine(n_examples=128, batch_units=1)  # 32 units
    # never 0: an (almost-)empty selection stays in the bucket family
    assert [eng.bucket_steps(n) for n in (0, 1, 4, 5, 9, 31, 32)] == \
        [4, 4, 4, 8, 12, 32, 32]
    idx = np.arange(10, dtype=np.int32)          # 30% subset
    plan = eng.subset_plan(idx, np.ones(10, np.float32), epoch=0)
    n_steps = plan[0].shape[0]
    assert n_steps == 12                          # not steps_per_epoch_max
    assert n_steps - 10 < eng.plan_granule
    assert int(eng.plan_live_steps(plan).sum()) == 10
    assert eng.epoch_cost(plan) == pytest.approx(10 / 32)
    # a selection smaller than one batch still pads into the bucket family
    # (an all-padding one-granule plan, not a fresh zero-length executable)
    m2, units2, tc2, eng2 = _lm_engine()         # batch_units=2
    tiny = eng2.subset_plan(np.asarray([0], np.int32),
                            np.ones(1, np.float32), epoch=0)
    assert tiny[0].shape == (eng2.plan_granule, eng2.batch_units)
    assert int(eng2.plan_live_steps(tiny).sum()) == 0
    assert eng2.epoch_cost(tiny) == 0.0
    p = m2.init_params(jax.random.PRNGKey(0))
    opt_init2, _ = make_update_for(tc2)
    o = opt_init2(p)
    leaf0 = np.asarray(jax.tree.leaves(p)[0])
    p2, o2, losses = eng2.run_epoch(p, o, tc2.lr, tiny)
    assert np.array_equal(leaf0, np.asarray(jax.tree.leaves(p2)[0]))
    assert int(o2["step"]) == 0                  # nothing advanced


# ---------------------------------------------------------------------------
# Padding rows are no-ops
# ---------------------------------------------------------------------------

#: where a padded plan's live rows sit among its 8 steps
LIVE_ROWS = {"trailing": [0, 1, 2], "leading": [5, 6, 7],
             "interleaved": [1, 4, 6]}


@pytest.mark.parametrize("where", sorted(LIVE_ROWS))
def test_padding_batches_are_bit_exact_noops(where):
    """A padded subset epoch must leave (params, opt_state) bit-identical
    to the unpadded epoch wherever its padding rows sit (the scan skips
    them), and the padding steps must report zero metric contribution."""
    m, units, tc, eng = _lm_engine()
    opt_init, _ = make_update_for(tc)
    idx = np.arange(6, dtype=np.int32)
    w = np.linspace(0.5, 2.0, 6).astype(np.float32)

    def run(pad_to_steps):
        p = m.init_params(jax.random.PRNGKey(1))
        o = opt_init(p)
        plan = eng.subset_plan(idx, w, epoch=0, pad_to_steps=pad_to_steps)
        if pad_to_steps:
            # move the live rows (the plan's first three) into place
            rows = LIVE_ROWS[where]
            order = rows + [r for r in range(pad_to_steps) if r not in rows]
            plan = tuple(jnp.asarray(a)[np.argsort(order)] for a in plan)
        p, o, losses = eng.run_epoch(p, o, tc.lr, plan)
        return p, o, losses, plan

    pp, po, lp, plan_pad = run(eng.steps_per_epoch_max)  # maximal padding
    assert eng.steps_per_epoch_max == 8
    assert np.flatnonzero(eng.plan_live_steps(plan_pad)).tolist() == \
        LIVE_ROWS[where]
    up, uo, lu, _ = run(0)                 # legacy unpadded shape
    for a, b in zip(jax.tree.leaves((pp, po)), jax.tree.leaves((up, uo))):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "padding steps advanced params/opt_state"
    live = eng.plan_live_steps(plan_pad)
    assert np.array_equal(np.asarray(lp)[live], np.asarray(lu))
    assert (np.asarray(lp)[~live] == 0.0).all()


@pytest.mark.slow
def test_padded_scan_matches_host_loop():
    """The padded scan epoch matches the legacy host loop over the same
    (unpadded) subset schedule; the host loop compiles its step
    independently, so parity is numerical (PR1 tolerance), not bitwise."""
    m, units, tc, eng = _lm_engine()
    opt_init, _ = make_update_for(tc)
    idx = np.arange(6, dtype=np.int32)
    w = np.linspace(0.5, 2.0, 6).astype(np.float32)

    p = m.init_params(jax.random.PRNGKey(1))
    o = opt_init(p)
    p, o, _ = eng.run_epoch(p, o, tc.lr, eng.subset_plan(idx, w, epoch=0))

    hp = m.init_params(jax.random.PRNGKey(1))
    ho = opt_init(hp)
    step_fn = make_train_step(m, tc)
    for batch in subset_iterator(units, idx, w, tc.seed, 0,
                                 eng.batch_units):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        hp, ho, _ = step_fn(hp, ho, batch, tc.lr)

    assert int(o["step"]) == int(ho["step"])     # padding: no counter ticks
    for a, b in zip(jax.tree.leaves(hp), jax.tree.leaves(p)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-3)


# ---------------------------------------------------------------------------
# Resident stage A parity
# ---------------------------------------------------------------------------

def _stage_a_parity(arch, atol):
    cfg = get_config(arch)
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init_params(key)
    units = _stacked_units(m, 8)
    proj = make_proj_for(m, key, 16, 16)
    pc = PGMConfig(subset_fraction=0.5, n_partitions=2,
                   sketch_dim_h=16, sketch_dim_v=16)
    g_host = units_gradients(m, params, units, proj)
    selector = ResidentSelector(m, pc, proj)
    g_res = selector.stage_a(params, units)
    assert g_res.shape == g_host.shape
    scale = float(jnp.abs(g_host).max())
    assert np.allclose(np.asarray(g_res), np.asarray(g_host),
                       atol=atol * max(scale, 1.0)), \
        float(jnp.abs(g_res - g_host).max())
    sel_h = pgm_select(m, params, units, pc, proj)
    sel_r = selector(params, units)
    assert np.asarray(sel_h.indices).tolist() == \
        np.asarray(sel_r.indices).tolist()
    assert np.allclose(np.asarray(sel_h.weights), np.asarray(sel_r.weights),
                       atol=1e-4)


def test_resident_stage_a_matches_host_lm():
    _stage_a_parity("starcoder2-3b-smoke", atol=1e-5)


@pytest.mark.slow
def test_resident_stage_a_matches_host_rnnt():
    _stage_a_parity("rnnt-crdnn-smoke", atol=1e-5)


def test_resident_selector_exact_mode():
    """Paper-faithful exact gradients also route through the batched
    scanned pass (no sketch projections)."""
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    units = _stacked_units(m, 4)
    pc = PGMConfig(subset_fraction=0.5, n_partitions=2, use_sketch=False)
    g_host = units_gradients(m, params, units, None, exact=True)
    g_res = ResidentSelector(m, pc, None).stage_a(params, units)
    assert np.allclose(np.asarray(g_res), np.asarray(g_host), atol=1e-5)


def test_resident_selector_reuses_one_stage_a_executable():
    """Across rounds (changing params, fixed unit shapes) stage A must be
    a jit cache hit — the projections are closed over the executable."""
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = _stacked_units(m, 8)
    proj = make_proj_for(m, jax.random.PRNGKey(3), 16, 16)
    pc = PGMConfig(subset_fraction=0.5, n_partitions=2)
    selector = ResidentSelector(m, pc, proj)
    p1 = m.init_params(jax.random.PRNGKey(0))
    p2 = m.init_params(jax.random.PRNGKey(1))
    selector(p1, units)
    misses0 = selector._stage_a._cache_size()
    selector(p2, units)
    assert selector._stage_a._cache_size() == misses0


# ---------------------------------------------------------------------------
# End-to-end loop wiring
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_train_with_resident_selection_matches_host_selection():
    cfg = get_config("starcoder2-3b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 32, 12, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=4)
    val = lm_units(make_lm_corpus(7, 16, 12, cfg.vocab_size), unit_size=4)
    tc = TrainConfig(
        lr=0.5, optimizer="sgd", epochs=4,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=24, sketch_dim_v=24))
    h_ref = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                 engine="scan")
    h_res = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                 engine="scan", resident_selection=True)
    assert np.allclose(h_ref.train_loss, h_res.train_loss, atol=1e-3)
    assert np.allclose(h_ref.val_loss, h_res.val_loss, atol=1e-3)
    for sr, ss in zip(h_ref.selections, h_res.selections):
        assert sr["indices"] == ss["indices"]
    assert h_ref.cost_units == pytest.approx(h_res.cost_units)
