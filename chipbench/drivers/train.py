"""Training window: back-to-back subset epochs through the program's
``EpochEngine.run_epoch``, one dispatch and one loss fetch per epoch.

Set-up builds one engine with its compiled epoch and its state, and
drives it from the seed through its first three steps with the window's
own call and plan shape: one plan whose first row is live and the rest
padding, then one with two live rows.  Those steps are what the check
compares with the reference, after the window; the window then continues
from their state on the subset's epochs.

Traffic keys: ``corpus`` (see ``chipbench/corpus.py``), ``unit_size``,
``batch_units``, ``subset_fraction`` and ``n_partitions`` (the subset is
PGM's budget, ``n_partitions * (subset_fraction * n_units //
n_partitions)`` units), ``optimizer`` (AdamW: lr, b1, b2, eps,
weight_decay, grad_clip) and ``limits`` of the check.
"""
from __future__ import annotations

import numpy as np

from chipbench import corpus, flops, weights
from chipbench.bench import Check
from chipbench.drivers import common
from chipbench.reference import crdnn as ref

#: steps that set-up drives and the check compares
CHECK_STEPS = 3


class Driver:
    def __init__(self, cell, seed):
        self.seed = seed
        self.cfg, self.tr = cell.config, cell.traffic
        if self.cfg["family"] != "rnnt" or self.tr["corpus"]["kind"] != "asr":
            raise ValueError("the train driver runs the rnnt family on an "
                             "asr corpus")

    # -- set-up ------------------------------------------------------------
    def setup(self, annotate):
        import jax
        from repro.configs.base import TrainConfig
        from repro.models.api import build_model
        from repro.train.engine import EpochEngine
        from repro.train.optim import make_update_for

        cfg, tr, opt = self.cfg, self.tr, self.tr["optimizer"]
        bundle = build_model(common.program_config(cfg))
        units, order = corpus.asr_units(tr["corpus"], cfg["n_feats"],
                                        cfg["vocab_size"], self.seed,
                                        tr["unit_size"])
        tc = TrainConfig(lr=opt["lr"], optimizer="adamw",
                         weight_decay=opt["weight_decay"],
                         grad_clip=opt["grad_clip"], seed=self.seed)
        self.eng = EpochEngine(bundle, tc, units,
                               batch_units=tr["batch_units"])
        n_units = self.eng.n_units
        per = int(tr["subset_fraction"] * n_units) // tr["n_partitions"]
        n_sel = per * tr["n_partitions"]
        if n_sel % tr["batch_units"]:
            raise ValueError(f"a subset of {n_sel} units does not fill "
                             f"batches of {tr['batch_units']}")
        self.sel_ids, self.sel_w = corpus.subset_by_rank(
            order, n_sel, tr["unit_size"], self.seed)

        self.wkey = jax.random.fold_in(corpus.seed_key(self.seed), 1)
        specs = ref.param_specs(cfg)
        params = weights.make(specs, self.wkey)
        common.check_tree(jax.eval_shape(bundle.init_params,
                                         jax.random.PRNGKey(0)), params)
        p0 = weights.make(specs, self.wkey)
        opt_init, _ = make_update_for(tc)
        opt_state = opt_init(params)
        self.lr = opt["lr"]

        # the check's three steps, through the window's call and feed: the
        # subset's first rows, padded to the window's plan shape
        plan0 = self.eng.subset_plan(self.sel_ids, self.sel_w, 0)
        n_steps, bu = plan0[0].shape
        first_rows = (np.asarray(plan0[0])[:CHECK_STEPS],
                      np.asarray(plan0[1])[:CHECK_STEPS])
        losses, rows, row_w = [], [], []
        for lo, hi in ((0, 1), (1, CHECK_STEPS)):
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
        self.delta_norms = np.asarray(common.diff_norms(params, p0))
        del p0
        self.params, self.opt_state = params, opt_state

        # what one subset epoch trains
        fl = np.asarray(units["feat_lens"])[self.sel_ids].ravel()
        tl = np.asarray(units["token_lens"])[self.sel_ids].ravel()
        self.epoch_frames = float(fl.sum())
        self.epoch_flops = flops.crdnn_train_flops(cfg, fl, tl)
        shp = corpus.asr_shape(tr["corpus"])
        self.lattice_shape = (flops.crdnn_encoder_frames(cfg, shp["T"]),
                              bu * tr["unit_size"], shp["U"] + 1)
        self.epoch = 0
        self.next_plan = self._plan()

    def _plan(self):
        self.epoch += 1
        return self.eng.subset_plan(self.sel_ids, self.sel_w, self.epoch)

    # -- window --------------------------------------------------------------
    def step(self, annotate) -> dict:
        with annotate("bench.dispatch"):
            self.params, self.opt_state, losses = self.eng.run_epoch(
                self.params, self.opt_state, self.lr, self.next_plan)
        with annotate("bench.plan"):
            self.next_plan = self._plan()
        with annotate("bench.fetch"):
            losses = np.asarray(losses)
        return {"ok": bool(np.all(np.isfinite(losses))),
                "frames": self.epoch_frames, "flops": self.epoch_flops}

    def end_to_end(self, window) -> dict:
        return {"asr_train_frames_per_s":
                window.totals["frames"] / window.seconds}

    def facts(self) -> dict:
        return {"lattice_shape": self.lattice_shape}

    def release(self):
        import jax
        rows, _ = self.check_rows
        ids = np.asarray(rows).reshape(-1)
        # the check's utterances, before the program's state goes
        self.check_batch = {
            k: jax.device_get(v[ids]).reshape((-1,) + v.shape[2:])
            for k, v in self.eng.units.items() if k != "weights"}
        del self.params, self.opt_state, self.eng

    # -- check ---------------------------------------------------------------
    def reference_inputs(self):
        """The check steps' batches, as (utterances, weights) pairs, and
        the initial weights, made again from the seed."""
        import jax.numpy as jnp
        rows, row_w = self.check_rows
        us = self.tr["unit_size"]
        n = rows.shape[1] * us
        batches = []
        for s in range(CHECK_STEPS):
            b = {k: jnp.asarray(v[s * n:(s + 1) * n])
                 for k, v in self.check_batch.items()}
            batches.append((b, jnp.asarray(np.repeat(row_w[s], us))))
        return batches, weights.make(ref.param_specs(self.cfg), self.wkey)

    def reference(self, batches, p0, dtype=None):
        """Losses, first clipped gradient's leaf norms and the leaf norms
        of the change after the check steps, by the reference."""
        import jax.numpy as jnp
        losses, g1, p3 = ref.train_steps(p0, self.cfg, batches,
                                         self.tr["optimizer"],
                                         dtype or jnp.float32)
        return (losses, np.asarray(common.leaf_norms(g1)),
                np.asarray(common.diff_norms(p3, p0)))

    @staticmethod
    def numbers(got, want) -> dict:
        """The compared numbers of readings ``got`` against ``want``.

        The loss is compared at the first step: the later steps follow
        AdamW's first, sign-like step, which turns the chip's rounding
        into a loss gap that swings from seed to seed (PERF.md, section
        4); the change after the check steps covers them.  Leaves whose
        reference gradient is under a thousandth of the median leaf's
        move by round-off alone under Adam, and are left out of the
        change."""
        keep = want[1] >= 1e-3 * np.median(want[1])
        return {"first_loss_gap": float(abs(got[0][0] - want[0][0])
                                        / abs(want[0][0])),
                "grad_gap": common.norm_gap(got[1], want[1]),
                "delta_gap": common.norm_gap(got[2], want[2], keep)}

    def readings(self):
        return (self.step_losses, self.g1_norms, self.delta_norms)

    def control_readings(self):
        """The control's readings: the reference put in the program's
        place, its network computed in bfloat16, the precision below the
        configuration's float32."""
        import jax.numpy as jnp
        return self.reference(*self.ref_inputs, dtype=jnp.bfloat16)

    def check(self) -> list:
        self.ref_inputs = self.reference_inputs()
        self.want = self.reference(*self.ref_inputs)
        lim = self.tr["limits"]
        return [Check(k, v, lim[k]) for k, v in
                self.numbers(self.readings(), self.want).items()]
