"""Per-architecture engine + selection test matrix (``make test-archs``).

Three layers, every arch family the repo carries (DESIGN.md §8):

* smoke — every assigned arch instantiates a REDUCED config, runs one
  forward and one train step, asserts shapes/finiteness; decoder archs
  additionally check prefill->decode consistency (slow tier: one compile
  per arch adds up to minutes);
* engine matrix — per-arch host-vs-scan history parity at rtol 1e-3 for
  the MoE pair (Mixtral/OLMoE) and the recurrent pair
  (RWKV6/RecurrentGemma), a 4-device subprocess sharded smoke for the
  MoE (expert-axis specs asserted on the sharded state) and one
  recurrent arch, and a resident PGM selection round per family —
  router-aware for MoE (``PGMConfig.moe_router_term``);
* dispatch regression — ``models/moe.py:_topk_dispatch`` gate-weight
  conservation at capacity 1 and exact slot occupancy under bf16 past
  256 tokens (the float-cumsum hazard).

Only the cheapest member of each family (Mixtral, RWKV6) runs in the
fast tier; the rest ride the slow tier / ``make test-archs``.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import lm_units
from repro.data.synthetic import make_lm_corpus
from repro.models.api import build_model
from repro.train.loop import train_with_selection
from repro.train.optim import make_optimizer, clip_by_global_norm

# the whole module is the per-arch matrix: `make test-archs` selects it
pytestmark = pytest.mark.archs

ARCHS = list_archs()
ROOT = os.path.join(os.path.dirname(__file__), "..")

# engine matrix rows: both MoE archs + both recurrent substrates; the
# cheapest member of each family stays in the fast tier, the sibling
# (same code paths, bigger smoke config) rides the slow tier
MATRIX = [
    "mixtral-8x7b",
    pytest.param("olmoe-1b-7b", marks=pytest.mark.slow),
    "rwkv6-3b",
    pytest.param("recurrentgemma-9b", marks=pytest.mark.slow),
]
RECURRENT_FAMILIES = ("ssm", "hybrid")


# ---------------------------------------------------------------------------
# Smoke layer (slow tier): every arch, one forward + one train step
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_train_step(arch):
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init_params(key)
    batch = m.make_batch(key, 2, 32)
    loss, metrics = m.loss_fn(params, batch)
    assert jnp.isfinite(loss), (arch, metrics)
    per_ex = m.per_example_loss(params, batch)
    assert per_ex.shape == (2,)
    assert jnp.isfinite(per_ex).all()

    # one SGD step decreases nothing catastrophic and keeps params finite
    opt_init, opt_update = make_optimizer("sgd")
    opt_state = opt_init(params)
    grads = jax.grad(lambda p: m.loss_fn(p, batch)[0])(params)
    grads, gnorm = clip_by_global_norm(grads, 5.0)
    assert jnp.isfinite(gnorm) and gnorm > 0
    params2, _ = opt_update(params, grads, opt_state, lr=0.1)
    loss2, _ = m.loss_fn(params2, batch)
    assert jnp.isfinite(loss2)


DECODER_ARCHS = [a for a in ARCHS
                 if get_config(a).family not in ("rnnt", "encdec", "vlm")]


@pytest.mark.slow
@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_prefill_decode_consistency(arch):
    from repro.models import transformer as T
    cfg = get_config(arch + "-smoke")
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    B, S = 2, 24
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    xfull = T.embed_tokens(params, cfg, tokens)
    hfull, _, _ = T.forward_hidden(params, cfg, xfull, remat=False)
    xpre = T.embed_tokens(params, cfg, tokens[:, :S])
    _, _, cache = T.forward_hidden(params, cfg, xpre, remat=False,
                                   collect_cache=True, cache_len=S + 4)
    xt = T.embed_tokens(params, cfg, tokens[:, S:S + 1])
    hdec, _ = T.decode_step(params, cfg, xt, cache)
    err = float(jnp.max(jnp.abs(hdec[:, 0] - hfull[:, S])))
    assert err < 5e-4, (arch, err)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["seamless-m4t-medium", "paligemma-3b"])
def test_frontend_archs_serve(arch):
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = m.init_params(key)
    batch = m.make_batch(key, 2, 24)
    logits, cache = m.prefill(params, batch, cache_len=32)
    assert logits.shape == (2, cfg.vocab_size)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache = m.decode(params, cache, tok)
    assert logits2.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits2).all()


@pytest.mark.slow
def test_rnnt_loss_decreases_with_training_signal():
    """The RNN-T on learnable synthetic speech: a few SGD steps reduce loss."""
    from repro.data.synthetic import make_asr_corpus
    from repro.data.pipeline import asr_units
    cfg = get_config("rnnt-crdnn-smoke")
    m = build_model(cfg)
    corpus = make_asr_corpus(0, 32, n_feats=cfg.rnnt.n_feats,
                             vocab_size=cfg.rnnt.vocab_size)
    units = asr_units(corpus, 4)
    batch = {k: jnp.asarray(v[0]) for k, v in units.items()}
    params = m.init_params(jax.random.PRNGKey(0))
    opt_init, opt_update = make_optimizer("adamw")
    opt = opt_init(params)
    first = last = None
    for i in range(8):
        (l, _), g = jax.value_and_grad(
            lambda p: m.loss_fn(p, batch), has_aux=True)(params)
        g, _ = clip_by_global_norm(g, 5.0)
        params, opt = opt_update(params, g, opt, lr=3e-3)
        first = first if first is not None else float(l)
        last = float(l)
    assert last < first, (first, last)


# ---------------------------------------------------------------------------
# Engine matrix: per-arch host-vs-scan parity (rtol 1e-3)
# ---------------------------------------------------------------------------

def _matrix_setup(arch, n=16, seq=10, epochs=3):
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, n, seq, cfg.vocab_size,
                                    hard_fraction=0.4), unit_size=2)
    val = lm_units(make_lm_corpus(7, 8, seq, cfg.vocab_size), unit_size=2)
    tc = TrainConfig(
        lr=0.2, optimizer="sgd", epochs=epochs,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2, select_every=2,
                      warm_start_epochs=1, sketch_dim_h=16, sketch_dim_v=16,
                      moe_router_term=(cfg.family == "moe")))
    return m, units, val, tc


@pytest.mark.parametrize("arch", MATRIX)
def test_engine_parity_matrix(arch):
    """Host loop and scanned engine walk the same trajectory — losses,
    selected indices and OMP weights — on every matrix arch, including
    the router-aware MoE selection term."""
    m, units, val, tc = _matrix_setup(arch)
    h_host = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="host")
    h_scan = train_with_selection(m, units, tc, method="pgm", val_units=val,
                                  engine="scan")
    assert np.allclose(h_host.train_loss, h_scan.train_loss,
                       rtol=1e-3, atol=1e-3), \
        (arch, h_host.train_loss, h_scan.train_loss)
    assert np.allclose(h_host.val_loss, h_scan.val_loss,
                       rtol=1e-3, atol=1e-3), (arch,)
    assert len(h_host.selections) == len(h_scan.selections) >= 1
    for sh, ss in zip(h_host.selections, h_scan.selections):
        assert sh["indices"] == ss["indices"], (arch, sh, ss)
        assert np.allclose(sh["weights"], ss["weights"],
                           rtol=1e-3, atol=1e-3)
    assert h_host.cost_units == pytest.approx(h_scan.cost_units)


# ---------------------------------------------------------------------------
# Engine matrix: resident PGM selection round per family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", MATRIX)
def test_resident_selection_round_matrix(arch):
    """One resident selection round per family: the jitted batch-scanned
    stage A matches the host per-unit path at 1e-3, and stage B returns
    a valid weighted subset.  MoE archs run with the router-aware term
    on (DESIGN.md §8)."""
    from repro.core.lastlayer import make_proj_for, units_gradients
    from repro.core.pgm import ResidentSelector
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size), unit_size=2)
    dev = {k: jnp.asarray(v) for k, v in units.items()}
    params = m.init_params(jax.random.PRNGKey(0))
    proj = make_proj_for(m, jax.random.PRNGKey(1), 16, 16)
    is_moe = cfg.family == "moe"
    pc = PGMConfig(subset_fraction=0.5, n_partitions=2,
                   sketch_dim_h=16, sketch_dim_v=16, moe_router_term=is_moe)
    sel_r = ResidentSelector(m, pc, proj)
    g_res = sel_r.stage_a(params, dev)
    g_host = units_gradients(m, params, dev, proj, router_term=is_moe)
    assert g_res.shape == g_host.shape == (8, g_host.shape[1])
    assert np.allclose(np.asarray(g_res), np.asarray(g_host),
                       rtol=1e-3, atol=1e-3)
    sel = sel_r(params, dev)
    assert int(sel.n_selected) == 4
    idx = np.asarray(sel.indices)
    assert ((idx >= -1) & (idx < 8)).all()
    live = idx >= 0
    assert np.isfinite(np.asarray(sel.weights)[live]).all()
    assert np.isfinite(np.asarray(sel.errors)).all()


@pytest.mark.parametrize("arch", ["mixtral-8x7b",
                                  pytest.param("olmoe-1b-7b",
                                               marks=pytest.mark.slow)])
def test_moe_router_term_definition(arch):
    """The router-aware MoE selection gradient (DESIGN.md §8): opt-in,
    appends one sketched block per router leaf after the head sketch —
    the default stays head-only (paper-faithful) — and the router block
    is non-degenerate (top-k dispatch + aux loss do reach the router)."""
    from repro.core.lastlayer import (make_proj_for, moe_router_grads,
                                      units_gradients)
    cfg = get_config(arch + "-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 8, 10, cfg.vocab_size), unit_size=2)
    dev = {k: jnp.asarray(v) for k, v in units.items()}
    params = m.init_params(jax.random.PRNGKey(0))
    proj = make_proj_for(m, jax.random.PRNGKey(1), 16, 16)
    g_head = units_gradients(m, params, dev, proj, router_term=False)
    g_full = units_gradients(m, params, dev, proj, router_term=True)
    assert g_full.shape[1] > g_head.shape[1], (g_full.shape, g_head.shape)
    # the head block is unchanged by appending the router block
    assert np.allclose(np.asarray(g_full[:, :g_head.shape[1]]),
                       np.asarray(g_head), rtol=1e-4, atol=1e-5)
    router_block = np.asarray(g_full[:, g_head.shape[1]:])
    assert np.isfinite(router_block).all()
    assert np.abs(router_block).max() > 0, "router receives no gradient"
    # definition check: per-unit autodiff grads over every router leaf
    unit0 = {k: v[0] for k, v in dev.items()}
    grads = moe_router_grads(m, params, unit0)
    assert len(grads) >= 1
    for g in grads:
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())


def test_moe_router_term_rejects_routerless_params():
    """A family='moe' bundle whose params lost their router leaves must
    fail loudly, not silently return a head-only representation."""
    from repro.core.lastlayer import moe_router_grads
    cfg = get_config("mixtral-8x7b-smoke")
    m = build_model(cfg)
    units = lm_units(make_lm_corpus(0, 2, 8, cfg.vocab_size), unit_size=1)
    unit0 = {k: jnp.asarray(v[0]) for k, v in units.items()}
    params = m.init_params(jax.random.PRNGKey(0))

    def drop_router(t):
        if isinstance(t, dict):
            return {k: drop_router(v) for k, v in t.items()
                    if k != "router"}
        if isinstance(t, (list, tuple)):
            return type(t)(drop_router(v) for v in t)
        return t

    with pytest.raises(ValueError, match="router"):
        moe_router_grads(m, drop_router(params), unit0)


# ---------------------------------------------------------------------------
# Engine matrix: 4-device subprocess sharded smokes (slow tier)
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
def test_moe_expert_sharded_engine_smoke():
    """Mixtral-smoke on a (2,2) ``data x expert`` mesh with
    ``spec_mode='expert'``: expert banks shard their leading E dim over
    the expert axis while the router stays replicated (asserted on the
    sharded state), and two training epochs stay within 1e-3 of the
    single-device engine."""
    out = _run(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        import jax.tree_util as jtu
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for
        assert jax.device_count() == 4
        cfg = get_config("mixtral-8x7b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size,
                                        hard_fraction=0.4), 2)
        tc = TrainConfig(lr=0.2, optimizer="sgd", epochs=2, pgm=PGMConfig())
        mesh = make_mesh((2, 2), ("data", "expert"))
        eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh,
                          spec_mode="expert")
        opt_init, _ = make_update_for(tc)
        p = m.init_params(jax.random.PRNGKey(0)); o = opt_init(p)
        p, o = eng.shard_state(p, o)
        # the expert banks shard E over 'expert'; the router replicates
        flat = jtu.tree_flatten_with_path(p)[0]
        n_expert = n_router = 0
        for path, leaf in flat:
            ks = jtu.keystr(path)
            spec = leaf.sharding.spec
            if ks.endswith("['w_in']") or ks.endswith("['w_out']") \\
                    or ks.endswith("['w_gate']"):
                assert "expert" in jtu.tree_leaves(tuple(spec)), (ks, spec)
                n_expert += 1
            if ks.endswith("['router']"):
                assert all(s is None for s in spec), (ks, spec)
                n_router += 1
        assert n_expert >= 2 and n_router >= 1, (n_expert, n_router)
        losses = []
        for e in range(tc.epochs):
            p, o, l = eng.run_epoch(p, o, tc.lr, eng.full_plan(e))
            losses.append(np.asarray(l))
        # single-device reference
        eng1 = EpochEngine(m, tc, units, batch_units=2)
        p1 = m.init_params(jax.random.PRNGKey(0)); o1 = opt_init(p1)
        for e in range(tc.epochs):
            p1, o1, l1 = eng1.run_epoch(p1, o1, tc.lr, eng1.full_plan(e))
            assert np.allclose(losses[e], np.asarray(l1),
                               rtol=1e-3, atol=1e-3), (e, losses[e], l1)
        print("MOE-EXPERT-SHARDED-OK")
    """))
    assert "MOE-EXPERT-SHARDED-OK" in out


@pytest.mark.slow
def test_recurrent_sharded_engine_smoke():
    """RWKV6-smoke on a 4-way pure-data mesh: the scan-of-scan (epoch
    scan over the time-recurrent forward) compiles and trains on the
    sharded engine within 1e-3 of single device."""
    out = _run(textwrap.dedent("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_config
        from repro.configs.base import PGMConfig, TrainConfig
        from repro.data.pipeline import lm_units
        from repro.data.synthetic import make_lm_corpus
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for
        assert jax.device_count() == 4
        cfg = get_config("rwkv6-3b-smoke")
        m = build_model(cfg)
        units = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size,
                                        hard_fraction=0.4), 2)
        tc = TrainConfig(lr=0.2, optimizer="sgd", epochs=2, pgm=PGMConfig())
        mesh = make_mesh((4,), ("data",))
        eng = EpochEngine(m, tc, units, batch_units=2, mesh=mesh)
        opt_init, _ = make_update_for(tc)
        p = m.init_params(jax.random.PRNGKey(0)); o = opt_init(p)
        p, o = eng.shard_state(p, o)
        eng1 = EpochEngine(m, tc, units, batch_units=2)
        p1 = m.init_params(jax.random.PRNGKey(0)); o1 = opt_init(p1)
        for e in range(tc.epochs):
            p, o, l = eng.run_epoch(p, o, tc.lr, eng.full_plan(e))
            p1, o1, l1 = eng1.run_epoch(p1, o1, tc.lr, eng1.full_plan(e))
            assert np.allclose(np.asarray(l), np.asarray(l1),
                               rtol=1e-3, atol=1e-3), (e, l, l1)
        print("RECURRENT-SHARDED-OK")
    """))
    assert "RECURRENT-SHARDED-OK" in out


# ---------------------------------------------------------------------------
# _topk_dispatch capacity regression (satellite a)
# ---------------------------------------------------------------------------

def test_topk_dispatch_conserves_gates_at_capacity_one():
    """Capacity 1, top-1: each expert keeps exactly its first-routed
    token per group; every kept token's combine weights sum to 1 (its
    whole top-k renormalized mass), dropped tokens to 0 — drop never
    redistributes mass to other tokens."""
    from repro.models.moe import _topk_dispatch
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 24, 4)), jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = _topk_dispatch(gates, top_k=1, capacity=1)
    occ = np.asarray(dispatch.sum(axis=(1, 3)))          # (G,E) tokens kept
    assert occ.max() <= 1.0 + 1e-6, occ
    tok_mass = np.asarray(combine.sum(axis=(2, 3)))      # (G,S)
    kept = np.asarray(dispatch.sum(axis=(2, 3))) > 0
    assert np.allclose(tok_mass[kept], 1.0, atol=1e-6), tok_mass[kept]
    assert np.allclose(tok_mass[~kept], 0.0), tok_mass[~kept]
    # top-2 partial drop: a token keeping one of two experts renormalizes
    # over the kept one only — still exactly mass 1
    d2, c2 = _topk_dispatch(gates, top_k=2, capacity=1)
    mass2 = np.asarray(c2.sum(axis=(2, 3)))
    kept_any = np.asarray(d2.sum(axis=(2, 3))) > 0
    assert np.allclose(mass2[kept_any], 1.0, atol=1e-6)


def test_topk_dispatch_bf16_positions_exact_past_256_tokens():
    """bf16 gates with >256 tokens per group: position bookkeeping must
    stay exact (int32) — the old float cumsum collided slot positions,
    multi-filling capacity slots."""
    from repro.models.moe import _topk_dispatch
    rng = np.random.default_rng(1)
    S, E = 600, 2
    logits = rng.normal(size=(1, S, E)).astype(np.float32)
    gates = jax.nn.softmax(jnp.asarray(logits, jnp.bfloat16)
                           .astype(jnp.float32), -1).astype(jnp.bfloat16)
    cap = 512
    dispatch, combine = _topk_dispatch(gates, top_k=1, capacity=cap)
    d = np.asarray(dispatch, np.float32)
    # every (expert, slot) cell holds at most one token ...
    assert d.sum(axis=1).max() <= 1.0 + 1e-6
    # ... and exactly min(S routed to e, cap) tokens are kept per expert
    routed = np.asarray(
        jax.nn.one_hot(jnp.argmax(gates.astype(jnp.float32), -1), E)
    ).sum(axis=1)[0]
    want_kept = np.minimum(routed, cap).sum()
    assert d.sum() == pytest.approx(want_kept), (d.sum(), want_kept)
    mass = np.asarray(combine.astype(jnp.float32).sum(axis=(2, 3)))
    kept = d.sum(axis=(2, 3)) > 0
    assert np.allclose(mass[kept], 1.0, atol=2e-2)  # bf16 round-trip
