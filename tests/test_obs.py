"""The program's instrumentation (``repro.obs``) at smoke widths on the
CPU: the layer scopes reach every matmul, convolution, custom call and
loop of the compiled RNN-T epoch, forward and backward; an epoch
dispatch records its counts from the plan's host copy, and whether it
compiled, without a backend compile or a host transfer of its own; the
spans reach a profiler trace with their counts."""
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro import obs
from repro.analysis.contracts import no_implicit_transfers
from repro.configs import get_config
from repro.configs.base import PGMConfig, TrainConfig
from repro.data.pipeline import asr_units, lm_units
from repro.data.synthetic import make_asr_corpus, make_lm_corpus
from repro.models.api import build_model
from repro.train.engine import EpochEngine
from repro.train.loop import train_with_selection
from repro.train.optim import make_update_for

#: the layer scopes, as PERF.md's layer map names them
MODEL_SCOPES = ("cnn", "encoder_lstm", "dnn", "pred_gru", "joint_proj")
LOSS_SCOPES = ("rnnt_loss.fwd", "rnnt_loss.bwd")
ENGINE_SCOPES = ("batch_gather", "grad_clip", "optimizer")
SCOPES = MODEL_SCOPES + LOSS_SCOPES + ENGINE_SCOPES
#: the epoch scan's own loop holds every step, and no scope
EPOCH_LOOP = "jit(run)/while"
HEAVY = ("dot", "convolution", "custom-call", "while")
_OP = re.compile(r"^\s*(?:ROOT )?(%\S+) = .*? ("
                 + "|".join(HEAVY) + r')\(.*?op_name="([^"]*)"', re.M)


def _under(scope: str, path: str) -> bool:
    return re.search(r"(?:^|[/(])%s(?:[)/]|$)" % re.escape(scope),
                     path) is not None


def _host_spans(trace_dir):
    """``[(name, {arg: value})]`` of the ``repro.*`` spans in a trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append((ev.name, dict(ev.stats)))
    return spans


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Backend compiles counted exactly: no program is loaded from a
    persistent compilation cache instead."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def epochs(tmp_path_factory, no_persistent_cache):
    """Two dispatches of one subset plan (two live steps, two padding
    rows) on a fresh engine, traced, with what each recorded."""
    cfg = get_config("rnnt-crdnn-smoke")
    bundle = build_model(cfg)
    units = asr_units(make_asr_corpus(0, 16, n_feats=cfg.rnnt.n_feats,
                                      vocab_size=cfg.rnnt.vocab_size), 2)
    tc = TrainConfig(lr=0.01, optimizer="adamw", grad_clip=5.0,
                     pgm=PGMConfig())
    eng = EpochEngine(bundle, tc, units, batch_units=2)
    opt_init, _ = make_update_for(tc)
    params = bundle.init_params(jax.random.PRNGKey(0))
    opt_state = opt_init(params)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    compiles, gated, records = [], [], []
    with jax.profiler.trace(trace_dir):
        plan = eng.subset_plan(np.array([5, 1, 6, 2]), np.ones(4), 0,
                               pad_to_steps=4)
        for _ in range(2):
            before = obs.value("compile.count")
            gated_before = obs.value("epoch.gated_steps")
            with no_implicit_transfers():
                params, opt_state, _ = eng.run_epoch(params, opt_state,
                                                     0.01, plan)
            compiles.append(obs.value("compile.count") - before)
            gated.append(obs.value("epoch.gated_steps") - gated_before)
            records.append(obs.dispatches()[-1])
    text = eng.lower_epoch(params, opt_state, 0.01, plan).compile().as_text()
    return {"eng": eng, "units": units, "plan": plan, "compiles": compiles,
            "gated": gated, "records": records, "text": text,
            "spans": _host_spans(trace_dir)}


def test_every_heavy_op_maps_to_a_layer_scope(epochs):
    ops = _OP.findall(epochs["text"])
    assert {kind for _, kind, _ in ops} >= {"dot", "convolution", "while"}
    for name, kind, path in ops:
        if path == EPOCH_LOOP:
            continue
        assert [s for s in SCOPES if _under(s, path)], (name, kind, path)
    paths = [path for _, _, path in ops]
    for scope in MODEL_SCOPES:       # forward and backward
        assert any(_under(f"jvp({scope})", p) for p in paths), scope
        assert any(_under(f"transpose(jvp({scope}))", p) for p in paths), \
            scope
    assert any(_under("jvp(rnnt_loss.fwd)", p) for p in paths)
    assert any(_under("transpose(jvp(rnnt_loss.bwd))", p) for p in paths)


def test_registered_map_is_the_compiled_epochs(epochs):
    record = epochs["records"][0]
    scopes = obs.scope_maps()[record.module]
    assert record.module.startswith("jit_run#")
    assert scopes == obs.parse_scopes(epochs["text"])
    for scope in ENGINE_SCOPES:
        assert any(_under(scope, p) for p in scopes.values()), scope


def test_first_dispatch_compiles_once_and_registration_adds_none(epochs):
    first, second = epochs["records"]
    assert (first.compiled, second.compiled) == (True, False)
    assert first.module == second.module
    assert epochs["compiles"] == [1, 0]


def test_record_counts_come_from_the_plan(epochs):
    idx = np.asarray(epochs["plan"][0])
    feat_lens = epochs["units"]["feat_lens"]        # (units, unit size)
    T = epochs["units"]["feats"].shape[2]
    live_rows = idx[:, 0] >= 0
    want_live = int(feat_lens[idx[live_rows]].sum())
    for r in epochs["records"]:
        assert (r.steps, r.live_steps) == (4, 2)
        assert r.positions == 4 * 2 * 2 * T
        assert r.live_positions == want_live
    assert tuple(epochs["plan"].counts) == (4, 2, 4 * 2 * 2 * T, want_live)
    # the padding rows the scan skips, counted per dispatch
    assert epochs["gated"] == [2, 2]


def test_spans_carry_their_counts(epochs):
    spans = epochs["spans"]
    dispatches = [a for n, a in spans if n == "repro.epoch.dispatch"]
    builds = [a for n, a in spans if n == "repro.plan.build"]
    counts = dict(epochs["plan"].counts._asdict())
    assert [a.pop("compiled") for a in dispatches] == [1, 0]
    assert dispatches == [dict(counts, gated_steps=2)] * 2
    assert builds == [counts]


def test_training_loop_spans(tmp_path):
    """What an operator reads in a trace of ``train_with_selection``:
    the round, the plan waits and the checkpoint submits beside the
    dispatches and plan builds."""
    cfg = get_config("starcoder2-3b-smoke")
    units = lm_units(make_lm_corpus(0, 16, 10, cfg.vocab_size), 2)
    tc = TrainConfig(lr=0.5, optimizer="sgd", epochs=3, pgm=PGMConfig(
        subset_fraction=0.5, n_partitions=2, select_every=2,
        warm_start_epochs=1, sketch_dim_h=16, sketch_dim_v=16))
    with jax.profiler.trace(str(tmp_path / "trace")):
        h = train_with_selection(build_model(cfg), units, tc, method="pgm",
                                 batch_units=2,
                                 ckpt_dir=str(tmp_path / "ckpt"))
    names = [n for n, _ in _host_spans(str(tmp_path / "trace"))]
    assert len(h.train_loss) == 3
    assert names.count("repro.select.round") == len(h.selections) == 1
    assert names.count("repro.epoch.dispatch") == 3
    assert names.count("repro.prefetch.wait") == 3
    assert names.count("repro.ckpt.submit") == 3
    assert names.count("repro.plan.build") >= 3
