"""Training window for any transducer configuration: the ``train`` driver's
window, check and comparison, with the plain reference and the operation
count that the configuration file names, and the engine placed on the
mesh that the traffic file names.

Configuration keys read besides the ``train`` driver's: ``reference``
(the plain reference's file, which gives ``param_specs`` and
``train_steps``, and ``init_stats`` for a model with batch norm);
``train_flops`` and ``encoder_frames`` (``module:function`` of the
operation count of utterances at their real lengths and of the encoder's
frame count; the CRDNN's of ``chipbench/flops.py`` where absent).

Traffic key ``mesh`` (optional): ``{"data": n}`` puts the engine on an
``n x 1`` ``data x model`` mesh of the cell's chips; ``batch_units`` is
then the global batch, split over ``data``, and the lattice kernel's
shape is a device's share of it.

A model with batch norm carries its running statistics in its params
(``repro.models.common.STATE_KEY``).  The benchmark makes them as the
reference starts them, and the check compares them after its steps:
``stats_gap``, the worst of the mean and the variance of
``|got - want|`` over ``|want - initial|`` (norms over all blocks).  The
change's leaf norms (``delta_gap``) cover the trained weights only.

``inert_grad_gap`` reads the first clipped gradient of the leaves that
the model makes inert, those whose reference gradient is under a
thousandth of the median leaf's (the leaves ``delta_gap`` leaves out):
the worst ``|got - want|`` over the median leaf's reference norm.  Their
gradient is zero but for round-off (the Conformer's depthwise bias, which
batch norm takes out again, and its key bias, which shifts every score
of a row alike), so what a run reads there is its own arithmetic's
round-off: float32's in the program, bfloat16's in the control (PERF.md,
section 4).  The check compares the numbers that the traffic's
``limits`` name.
"""
from __future__ import annotations

import importlib

import numpy as np

from chipbench import corpus, weights
from chipbench.drivers import common, train

#: where a configuration file names no operation count (the CRDNN's)
DEFAULT_FLOPS = {"train_flops": "chipbench.flops:crdnn_train_flops",
                 "encoder_frames": "chipbench.flops:crdnn_encoder_frames"}


def _module(path: str):
    """The module of a file under the checkout, as ``chipbench/x/y.py``."""
    name = path[:-3] if path.endswith(".py") else path
    return importlib.import_module(name.replace("/", "."))


def _function(spec: str):
    mod, fn = spec.split(":")
    return getattr(importlib.import_module(mod), fn)


def _split_state(tree):
    """(trained params, state), as the program splits them; a program that
    keeps no model state has none."""
    try:
        from repro.models.common import split_state
    except ImportError:
        return tree, None
    return split_state(tree)


class Driver(train.Driver):
    def __init__(self, cell, seed):
        super().__init__(cell, seed)
        self.ref = _module(self.cfg["reference"])
        self.train_flops, self.encoder_frames = (
            _function(self.cfg.get(k, v)) for k, v in DEFAULT_FLOPS.items())
        self.chips = cell.chips
        # a program without this architecture fails here, before any work
        self.program_cfg = common.program_config(self.cfg)

    def _mesh(self):
        shape = self.tr.get("mesh")
        if not shape:
            return None
        import jax

        from repro.launch.mesh import make_mesh
        n = int(shape["data"])
        if n != self.chips:
            raise ValueError(f"a data mesh of {n} on {self.chips} chips")
        return make_mesh((n, 1), ("data", "model"),
                         devices=jax.devices()[:n])

    # -- set-up ------------------------------------------------------------
    def setup(self, annotate):
        import jax
        from repro.configs.base import TrainConfig
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for

        cfg, tr, opt = self.cfg, self.tr, self.tr["optimizer"]
        bundle = build_model(self.program_cfg)
        units, order = corpus.asr_units(tr["corpus"], cfg["n_feats"],
                                        cfg["vocab_size"], self.seed,
                                        tr["unit_size"])
        tc = TrainConfig(lr=opt["lr"], optimizer="adamw",
                         weight_decay=opt["weight_decay"],
                         grad_clip=opt["grad_clip"], seed=self.seed)
        mesh = self._mesh()
        self.eng = EpochEngine(bundle, tc, units,
                               batch_units=tr["batch_units"], mesh=mesh)
        # on a mesh the engine keeps its own sharded copy of the corpus;
        # the whole corpus as it was made, on the first chip, would leave
        # too little memory there for the epoch's program
        feat_lens = np.asarray(units["feat_lens"])
        token_lens = np.asarray(units["token_lens"])
        del units
        n_units = self.eng.n_units
        per = int(tr["subset_fraction"] * n_units) // tr["n_partitions"]
        n_sel = per * tr["n_partitions"]
        if n_sel % tr["batch_units"]:
            raise ValueError(f"a subset of {n_sel} units does not fill "
                             f"batches of {tr['batch_units']}")
        self.sel_ids, self.sel_w = corpus.subset_by_rank(
            order, n_sel, tr["unit_size"], self.seed)

        self.wkey = jax.random.fold_in(corpus.seed_key(self.seed), 1)
        specs = self.ref.param_specs(cfg)
        trained_shapes, state_shapes = _split_state(
            jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0)))
        params = weights.make(specs, self.wkey)
        common.check_tree(trained_shapes, params)
        # the optimizer's state mirrors the trained params alone
        opt_init, _ = make_update_for(tc)
        opt_state = opt_init(params)
        self.stats0 = None
        if state_shapes is not None:
            stats0 = self.ref.init_stats(cfg)
            common.check_tree(state_shapes, stats0)
            # a host copy: the params' buffers are donated to the steps
            self.stats0 = jax.tree.map(np.asarray, stats0)
            from repro.models.common import merge_state
            params = merge_state(params, stats0)
        p0 = weights.make(specs, self.wkey)
        params, opt_state = self.eng.shard_state(params, opt_state)
        self.lr = opt["lr"]

        # the check's three steps, through the window's call and feed: the
        # subset's first rows, padded to the window's plan shape
        plan0 = self.eng.subset_plan(self.sel_ids, self.sel_w, 0)
        n_steps, bu = plan0[0].shape
        first_rows = (np.asarray(plan0[0])[:train.CHECK_STEPS],
                      np.asarray(plan0[1])[:train.CHECK_STEPS])
        losses, rows, row_w = [], [], []
        for lo, hi in ((0, 1), (1, train.CHECK_STEPS)):
            plan = self.eng.subset_plan(first_rows[0][lo:hi].ravel(),
                                        first_rows[1][lo:hi].ravel(), 0,
                                        pad_to_steps=n_steps)
            rows.append(np.asarray(plan[0])[: hi - lo])
            row_w.append(np.asarray(plan[1])[: hi - lo])
            with annotate("bench.dispatch"):
                params, opt_state, ls = self.eng.run_epoch(
                    params, opt_state, self.lr, plan)
            with annotate("bench.fetch"):
                losses += [float(x) for x in np.asarray(ls)[: hi - lo]]
            if lo == 0:
                self.g1_norms = np.asarray(common.leaf_norms(
                    opt_state["m"])) / (1.0 - opt["b1"])
        self.check_rows = (np.concatenate(rows), np.concatenate(row_w))
        self.step_losses = losses
        trained, stats = _split_state(params)
        self.delta_norms = np.asarray(common.diff_norms(trained, p0))
        self.stats = (None if stats is None
                      else jax.tree.map(np.asarray, jax.device_get(stats)))
        del p0, trained
        self.params, self.opt_state = params, opt_state

        # what one subset epoch trains
        fl = feat_lens[self.sel_ids].ravel()
        tl = token_lens[self.sel_ids].ravel()
        self.epoch_frames = float(fl.sum())
        self.epoch_flops = self.train_flops(cfg, fl, tl)
        shp = corpus.asr_shape(tr["corpus"])
        n_data = 1 if mesh is None else mesh.shape["data"]
        self.lattice_shape = (self.encoder_frames(cfg, shp["T"]),
                              bu * tr["unit_size"] // n_data, shp["U"] + 1)
        self.epoch = 0
        self.next_plan = self._plan()

    # -- check ---------------------------------------------------------------
    def reference_inputs(self):
        """The check steps' batches, as (utterances, weights) pairs, and
        the initial weights, made again from the seed by the
        configuration's reference."""
        import jax.numpy as jnp
        rows, row_w = self.check_rows
        us = self.tr["unit_size"]
        n = rows.shape[1] * us
        batches = []
        for s in range(train.CHECK_STEPS):
            b = {k: jnp.asarray(v[s * n:(s + 1) * n])
                 for k, v in self.check_batch.items()}
            batches.append((b, jnp.asarray(np.repeat(row_w[s], us))))
        return batches, weights.make(self.ref.param_specs(self.cfg),
                                     self.wkey)

    def reference(self, batches, p0, dtype=None):
        """The reference's readings: losses, first clipped gradient's
        leaf norms, the leaf norms of the change after the check steps,
        and the running statistics after them (None without)."""
        import jax.numpy as jnp
        out = self.ref.train_steps(p0, self.cfg, batches,
                                   self.tr["optimizer"],
                                   dtype or jnp.float32)
        losses, g1, p3 = out[:3]
        stats = None if len(out) < 4 else {
            k: np.asarray(v) for k, v in out[3].items()}
        return (losses, np.asarray(common.leaf_norms(g1)),
                np.asarray(common.diff_norms(p3, p0)), stats)

    def readings(self):
        # ``train.Driver.readings`` is looked up at call time, so that
        # ``faults.control``'s patch of it (the control's readings, which
        # ``reference`` gives with their statistics) reaches this driver
        got = super().readings()
        return got if len(got) == 4 else (*got, self.stats)

    def numbers(self, got, want) -> dict:
        """The ``train`` driver's numbers, ``stats_gap`` and
        ``inert_grad_gap``, those that the traffic's ``limits`` name: a
        cell leaves out a number that cannot be held to a limit (PERF.md,
        section 4)."""
        nums = train.Driver.numbers(got[:3], want[:3])
        if want[3] is not None:
            nums["stats_gap"] = max(
                float(np.linalg.norm(got[3][k] - want[3][k])
                      / np.linalg.norm(want[3][k] - self.stats0[k]))
                for k in want[3])
        nums["inert_grad_gap"] = inert_gap(got[1], want[1])
        return {k: v for k, v in nums.items() if k in self.tr["limits"]}


def inert_gap(got, want) -> float:
    """Of the leaves whose reference norm is under a thousandth of the
    median leaf's, the worst ``|got - want|`` over the median leaf's
    reference norm (0 where there are none)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.median(want)
    inert = want < 1e-3 * floor
    return float(np.max(np.abs(got - want)[inert], initial=0.0) / floor)
