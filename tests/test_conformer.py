"""The Conformer transducer (``models/conformer.py``) at small widths on the
system's normal path, against the plain reference
(``chipbench/reference/conformer.py``, which imports nothing of the
program): scanned epochs with batch norm's running statistics as
non-trained state, padding invariance, evaluation-mode selection
gradients, ``train_with_selection``, checkpoints and streaming decode.

Tolerances: program and reference run in float32 on the CPU, the
reference at ``highest`` precision; they differ by summation order only,
so losses, gradients and statistics agree to 1e-5 relative (a leaf held
to the larger of its own scale and the median leaf's, since some leaves'
gradients are zero but for round-off), and the norms of the weights'
change after AdamW steps to 1e-3 (AdamW's first steps are sign-like,
which lifts the round-off of small gradient coordinates).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.models.api import build_model
from repro.models.common import STATE_KEY, split_state
from repro.train.engine import EpochEngine
from repro.train.optim import make_update_for

ARCH = "conformer-l-transducer-smoke"
OPT = {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.0,
       "grad_clip": 5.0}


@pytest.fixture(scope="module")
def model():
    cfg = get_config(ARCH)
    bundle = build_model(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    return cfg, bundle, params


def _units(cfg, n=16, T=36, U=5, seed=0):
    """``n`` one-utterance units of live lengths in [5, T] (frames past an
    utterance's length are zero, as the corpus makes them)."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(5, T + 1, n)
    live = np.arange(T)[None, :, None] < lens[:, None, None]
    feats = rng.normal(size=(n, T, cfg.rnnt.n_feats)) * live
    u_lens = rng.integers(1, U + 1, n)
    toks = rng.integers(1, cfg.rnnt.vocab_size, (n, U)) \
        * (np.arange(U)[None] < u_lens[:, None])
    return {"feats": feats[:, None].astype(np.float32),
            "feat_lens": lens[:, None].astype(np.int32),
            "tokens": toks[:, None].astype(np.int32),
            "token_lens": u_lens[:, None].astype(np.int32),
            "weights": np.ones((n, 1), np.float32)}


def _ref_cfg(cfg):
    from repro.models import conformer
    return dict({k: v for k, v in dataclasses.asdict(cfg.rnnt).items()
                 if isinstance(v, (int, float))},
                bn_momentum=conformer.BN_MOMENTUM, bn_eps=conformer.BN_EPS)


def _tc():
    return TrainConfig(lr=OPT["lr"], optimizer="adamw",
                       grad_clip=OPT["grad_clip"], weight_decay=0.0)


def _close(a, b, rtol, floor=1e-12):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), floor)
    assert float(np.max(np.abs(a - b))) <= rtol * scale, \
        (float(np.max(np.abs(a - b))), scale)


def _close_tree(got, want, rtol):
    """Leafwise, against the larger of the leaf's own scale and the
    median leaf's: a leaf whose value is zero but for round-off (the key
    bias under the softmax, the depthwise bias under batch norm) is held
    to the tree's scale, not to its own noise."""
    scales = [float(np.max(np.abs(np.asarray(w))))
              for w in jax.tree.leaves(want)]
    floor = float(np.median(scales))
    jax.tree.map(lambda a, b: _close(a, b, rtol, floor), got, want)


def _change_norms(p, p0):
    return jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)), p, p0)


def test_epoch_matches_plain_reference(model):
    """Two AdamW steps through ``EpochEngine.run_epoch`` (a plan padded
    with two padding rows) against the reference: the losses, the weights
    and the running statistics after the steps."""
    from chipbench.reference import conformer as ref
    cfg, bundle, params = model
    units = _units(cfg)
    eng = EpochEngine(bundle, _tc(), units, batch_units=4)
    opt_init, _ = make_update_for(_tc())
    p, o = jax.tree.map(jnp.array, params), opt_init(split_state(params)[0])
    plan = eng.subset_plan(np.asarray([3, 0, 7, 12, 5, 9, 1, 14]),
                           np.linspace(0.5, 1.5, 8, dtype=np.float32), 0,
                           pad_to_steps=4)
    p, o, losses = eng.run_epoch(p, o, OPT["lr"], plan)
    idx, w = np.asarray(plan[0]), np.asarray(plan[1])
    batches = [({k: jnp.asarray(v[idx[s], 0]) for k, v in units.items()
                 if k != "weights"}, jnp.asarray(w[s])) for s in range(2)]
    trained0 = split_state(params)[0]
    want_l, want_g1, want_p, want_stats = ref.train_steps(
        trained0, _ref_cfg(cfg), batches, OPT)
    _close(np.asarray(losses)[:2], want_l, 1e-5)
    assert np.all(np.asarray(losses)[2:] == 0.0)
    trained, stats = split_state(p)
    # AdamW's first steps are sign-like, so the weights are compared by
    # the norm of each leaf's change, as the benchmark's check does,
    # leaving out the leaves whose gradient is zero but for round-off
    g = jax.tree.leaves(jax.tree.map(jnp.linalg.norm, want_g1))
    keep = np.asarray(g) >= 1e-3 * np.median(g)
    got_d = np.asarray(jax.tree.leaves(_change_norms(trained, trained0)))
    want_d = np.asarray(jax.tree.leaves(_change_norms(want_p, trained0)))
    _close(got_d[keep], want_d[keep], 1e-3)
    # the second step's batch statistics are of a forward through the
    # AdamW-stepped weights, so they inherit that step's round-off
    _close_tree(stats, want_stats, 1e-4)


def test_first_gradient_and_statistics_match_reference(model):
    """One step: AdamW's first moment over (1 - b1) is the clipped
    gradient; it and the running statistics after the step match the
    reference's."""
    from chipbench.reference import conformer as ref
    cfg, bundle, params = model
    units = _units(cfg, seed=1)
    eng = EpochEngine(bundle, _tc(), units, batch_units=4)
    opt_init, _ = make_update_for(_tc())
    p, o = jax.tree.map(jnp.array, params), opt_init(split_state(params)[0])
    row = np.asarray([2, 6, 10, 11], np.int32)
    plan = eng.subset_plan(row, np.ones(4, np.float32), 0, pad_to_steps=2)
    p, o, _ = eng.run_epoch(p, o, OPT["lr"], plan)
    batch = {k: jnp.asarray(v[row, 0]) for k, v in units.items()
             if k != "weights"}
    _, want_g1, _, want_stats = ref.train_steps(
        split_state(params)[0], _ref_cfg(cfg),
        [(batch, jnp.ones((4,), jnp.float32))], OPT)
    got_g1 = jax.tree.map(lambda m: m / (1.0 - OPT["b1"]), o["m"])
    _close_tree(got_g1, want_g1, 1e-5)
    _close_tree(p[STATE_KEY], want_stats, 1e-5)


def test_padding_rows_leave_statistics_alone(model):
    """A plan's padding rows run no step: the running statistics after a
    padded plan are those after its live rows alone, bit for bit, and an
    all-padding plan leaves them as they were."""
    cfg, bundle, params = model
    units = _units(cfg, seed=2)
    eng = EpochEngine(bundle, _tc(), units, batch_units=4)
    opt_init, _ = make_update_for(_tc())
    ids = np.arange(8)
    outs = []
    for pad in (2, 6):
        p = jax.tree.map(jnp.array, params)
        o = opt_init(split_state(params)[0])
        plan = eng.subset_plan(ids, np.ones(8, np.float32), 0,
                               pad_to_steps=pad)
        p, _, _ = eng.run_epoch(p, o, OPT["lr"], plan)
        outs.append(jax.device_get(p[STATE_KEY]))
    jax.tree.map(np.testing.assert_array_equal, outs[0], outs[1])
    p, o = jax.tree.map(jnp.array, params), opt_init(split_state(params)[0])
    empty = eng.subset_plan(np.full(4, -1), np.zeros(4, np.float32), 0,
                            pad_to_steps=2)
    p, _, _ = eng.run_epoch(p, o, OPT["lr"], empty)
    jax.tree.map(np.testing.assert_array_equal, jax.device_get(
        p[STATE_KEY]), jax.device_get(params[STATE_KEY]))


@pytest.mark.parametrize("mode", ["train", "eval"])
def test_padding_invariance(model, mode):
    """One utterance padded to two lengths (one of them not a multiple of
    4) gives the same loss and gradient, in training (batch statistics)
    and in evaluation (running statistics)."""
    cfg, bundle, params = model
    u = _units(cfg, n=1, T=23, seed=3)
    got = []
    for T in (23, 40):
        b = {k: jnp.asarray(v[:, 0]) for k, v in u.items()}
        b["feats"] = jnp.pad(b["feats"], ((0, 0), (0, T - 23), (0, 0)))
        if mode == "train":
            f = lambda p: bundle.loss_fn(p, b)[0]
        else:
            f = lambda p: bundle.per_example_loss(p, b).sum()
        got.append(jax.value_and_grad(f)(params))
    _close(got[0][0], got[1][0], 1e-5)
    _close_tree(got[0][1], got[1][1], 1e-5)


def test_selection_gradients_do_not_couple_utterances(model):
    """PGM's joint gradient reads the running statistics: a batch's
    gradient is the mean of its utterances' own, whatever their
    companions.  With batch statistics (the training forward) it is not,
    which is why selection must not use them."""
    from repro.core.lastlayer import rnnt_joint_grad
    cfg, bundle, params = model
    u = _units(cfg, n=3, seed=4)
    b = {k: jnp.asarray(v[:, 0]) for k, v in u.items()}
    one = lambda i: {k: v[i:i + 1] for k, v in b.items()}
    pair = {k: v[:2] for k, v in b.items()}
    g_pair = rnnt_joint_grad(bundle, params, pair)
    g_mean = (rnnt_joint_grad(bundle, params, one(0))
              + rnnt_joint_grad(bundle, params, one(1))) / 2
    _close(g_pair, g_mean, 1e-5)
    # the training forward couples them through batch norm
    head = lambda w, bb: bundle.loss_fn(
        dict(params, joint=dict(params["joint"], w_out=w)), bb)[0]
    w = params["joint"]["w_out"]
    t_pair = jax.grad(head)(w, pair)
    t_mean = (jax.grad(head)(w, one(0)) + jax.grad(head)(w, one(1))) / 2
    assert float(jnp.max(jnp.abs(t_pair - t_mean))) > 1e-4


def test_train_with_selection_smoke(tmp_path):
    """PGM training from ``train_with_selection`` (warm start, resident
    selection rounds, a checkpoint each epoch): finite losses, running
    statistics moved off their start, and a resumed run that ends on the
    uninterrupted run's weights and statistics."""
    from repro.train.loop import train_with_selection
    cfg = get_config(ARCH)
    bundle = build_model(cfg)
    units = _units(cfg, n=16, seed=5)
    val = _units(cfg, n=4, seed=6)
    tc = dataclasses.replace(
        _tc(), epochs=4, seed=3,
        pgm=PGMConfig(subset_fraction=0.5, n_partitions=2,
                      warm_start_epochs=1, select_every=2))
    ck = str(tmp_path / "ck")
    full = train_with_selection(bundle, units, tc, val_units=val,
                                batch_units=4, resident_selection=True,
                                ckpt_dir=ck)
    assert all(np.isfinite(full.train_loss)) and len(full.train_loss) == 4
    assert all(np.isfinite(full.val_loss))
    stats = full.final_params[STATE_KEY]
    assert float(jnp.max(jnp.abs(stats["var"] - 1.0))) > 1e-4
    # drop the last two epochs' checkpoints and resume from epoch 1
    import shutil

    from repro.train import checkpoint
    for s in (2, 3):
        shutil.rmtree(f"{ck}/step_{s}")
    checkpoint._update_latest(ck, 1)
    resumed = train_with_selection(bundle, units, tc, val_units=val,
                                   batch_units=4, resident_selection=True,
                                   ckpt_dir=ck, resume=True)
    assert resumed.train_loss == full.train_loss[2:]
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(resumed.final_params),
                 jax.device_get(full.final_params))


def test_checkpoint_round_trips_running_statistics(model, tmp_path):
    from repro.train import checkpoint
    cfg, bundle, params = model
    p = dict(params, **{STATE_KEY: {
        "mean": params[STATE_KEY]["mean"] + 0.25,
        "var": params[STATE_KEY]["var"] * 3.0}})
    o = make_update_for(_tc())[0](split_state(p)[0])
    assert STATE_KEY not in o["m"]
    checkpoint.save(str(tmp_path), 7, {"params": p, "opt": o})
    got, _ = checkpoint.restore(str(tmp_path), template={"params": p,
                                                         "opt": o})
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(got["params"][STATE_KEY]),
                 jax.device_get(p[STATE_KEY]))


def test_pred_step_matches_predict(model):
    """The LSTM prediction network stepped token by token reproduces the
    batch ``predict`` rows exactly."""
    from repro.models import rnnt as rnnt_mod
    cfg, bundle, params = model
    toks = jnp.asarray([[3, 9, 1, 14]], jnp.int32)
    ref = rnnt_mod.predict(params, cfg, toks)
    g, h = rnnt_mod.pred_start(params, cfg, 1)
    assert h.shape == (1, cfg.rnnt.pred_state)
    rows = [g]
    for u in range(toks.shape[1]):
        g, h = rnnt_mod.pred_step(params, cfg, toks[:, u], h)
        rows.append(g)
    np.testing.assert_allclose(np.asarray(jnp.stack(rows, 1)),
                               np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_streaming_decode_matches_reference(model):
    """The slot engine's streaming greedy decode of the Conformer (the
    encoder in evaluation mode, with the utterances' lengths) matches the
    per-frame host loop token for token."""
    from repro.serve.engine import Request, SlotEngine, rnnt_greedy_reference
    cfg, bundle, params = model
    F = cfg.rnnt.n_feats
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, inputs={"feats": rng.normal(
                size=(L, F)).astype(np.float32)}, max_new_tokens=128)
            for i, L in enumerate([40, 25, 33])]
    eng = SlotEngine(bundle, params, n_slots=2, max_new_tokens=128,
                     max_prompt_len=64, sync_every=4, max_symbols=8)
    got = {c.uid: c.tokens for c in eng.run(reqs)}
    for r in reqs:
        L = r.inputs["feats"].shape[0]
        feats = np.zeros((1, eng.bucket_for(r), F), np.float32)
        feats[0, :L] = r.inputs["feats"]
        assert got[r.uid] == rnnt_greedy_reference(
            bundle, params, feats, np.asarray([L]), max_symbols=8)[0]


def _shifted_position_scores(qv, w_pos):
    """The position term as Transformer-XL forms it, kept as the oracle:
    scores against all ``2T - 1`` relative positions ``T-1 ... -(T-1)``,
    moved to ``(T, T)`` by the pad-and-reshape shift."""
    B, T, H, dh = qv.shape
    d = H * dh
    rel = jnp.arange(T - 1, -T, -1, dtype=jnp.float32)
    ang = rel[:, None] * 10000.0 ** (-jnp.arange(0, d, 2,
                                                 dtype=jnp.float32) / d)
    pos = (jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1) @ w_pos
           ).reshape(2 * T - 1, H, dh)
    x = jnp.einsum("bihd,khd->bhik", qv, pos)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (1, 0)))
    x = x.reshape(B, H, 2 * T, T)[:, :, 1:]
    return x.reshape(B, H, T, 2 * T - 1)[..., :T]


def _mhsa_case(T, B, H, seed=7):
    """Random MHSA parameters (``u`` and ``v`` too) of the smoke width at
    ``H`` heads, an input of ``T`` frames, and live lengths with the
    first utterance's last keys masked."""
    from repro.models import conformer
    r = dataclasses.replace(get_config(ARCH).rnnt, conformer_heads=H)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = conformer._init_block(ks[0], r)["mhsa"]
    d = r.conformer_dim
    p["pos_u"] = jax.random.normal(ks[1], (H, d // H))
    p["pos_v"] = jax.random.normal(ks[2], (H, d // H))
    x = jax.random.normal(ks[3], (B, T, d))
    lens = jnp.asarray([max(1, T - 3)] + [T] * (B - 1))
    return p, x, conformer._live(lens, T), H


# The angle-difference form departs from the shift by float32 rounding
# only: sixteen units of the last place for the sums, plus the rounding of
# the angles i w, which grows with the position (T' units at T').
@pytest.mark.parametrize("T,B,H", [(1, 2, 4), (2, 2, 4), (7, 2, 4),
                                   (613, 1, 2)])
def test_position_term_matches_shifted_oracle(T, B, H, monkeypatch):
    """The position term by the angle-difference identity equals the
    pad-and-reshape shift of the scores against every relative position,
    and ``_mhsa`` with it gives the shift's outputs and gradients with
    respect to ``w_pos``, ``pos_v`` and ``wq``, with keys masked."""
    from repro.models import conformer
    p, x, live, H = _mhsa_case(T, B, H)
    d = x.shape[-1]
    qv = jax.random.normal(jax.random.PRNGKey(T), (B, T, H, d // H))
    ct = jax.random.normal(jax.random.PRNGKey(T + 1), x.shape)

    def outputs(position_scores):
        # a function of its own for each form, so that jit traces each
        monkeypatch.setattr(conformer, "_position_scores", position_scores)

        def run():
            f = lambda q: conformer._mhsa(q, x, live, H)
            g = jax.grad(lambda q: jnp.sum(f(q) * ct))(p)
            return (position_scores(qv, p["w_pos"]), f(p),
                    {k: g[k] for k in ("w_pos", "pos_v", "wq")})
        return jax.jit(run)()

    with jax.default_matmul_precision("highest"):
        got = outputs(conformer._position_scores)
        want = outputs(_shifted_position_scores)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, w, 2.0 ** -24 * (16 + T))


def test_block_lowering_has_no_relative_position_scores(monkeypatch):
    """One block's forward and backward, lowered at a small T', hold no
    array of trailing shape (T', 2T' - 1): the shift's scores against every
    relative position do not come back.  The oracle's do show there."""
    import re

    from repro.models import conformer
    cfg = get_config(ARCH)
    r, T = cfg.rnnt, 9
    p = jax.tree.map(lambda a: a[0], conformer.init_params(
        r, jax.random.PRNGKey(0))["blocks"])
    stats = conformer.init_stats(r)
    x = jnp.ones((2, T, r.conformer_dim))
    live = conformer._live(jnp.asarray([T, 5]), T)

    def lowered():
        def loss(p, x):
            y, _ = conformer._block(p, x, live, stats["mean"][0],
                                    stats["var"][0], r, True)
            return jnp.sum(y * y)
        return jax.jit(jax.grad(loss, (0, 1))).lower(p, x).as_text()

    pattern = re.compile(rf"(?<![0-9]){T}x{2 * T - 1}x[a-z]")
    assert not pattern.search(lowered())
    monkeypatch.setattr(conformer, "_position_scores",
                        _shifted_position_scores)
    assert pattern.search(lowered())
