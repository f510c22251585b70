"""End-to-end training loop implementing paper Algorithm 1 around any
ModelBundle: warm-start on full data, re-selection every R epochs
(PGM or a baseline), weighted mini-batch SGD on the subset, newbob lr
annealing on validation loss, checkpoint/resume, and cost accounting
(the basis of the paper's speedup numbers).

Execution is delegated to one engine interface
(``train/engine.py:make_engine``) with selection/annealing/checkpoint
logic shared above it:

  * ``engine="scan"`` (default) — the device-resident scanned epoch
    engine: units live on device, each epoch is one donated
    jit(lax.scan) over a precomputed batch plan, validation is one
    vmapped call.  With ``mesh`` the same executable compiles
    mesh-natively (FSDP/TP carry, data-sharded batches/units,
    DESIGN.md §5).
  * ``engine="host"`` — the legacy per-batch host loop, kept as the
    parity oracle (tests/test_train_engine.py proves the two produce
    the same losses and selections).

``epoch_chunk > 1`` folds up to that many consecutive epochs into one
``run_epochs`` dispatch (scan engine only): validation and the newbob
update run on device inside the chunk and metrics are fetched once per
chunk, so selection rounds (and checkpoint writes, once per chunk) are
the only host sync points.  ``plan_prefetch`` (default on for the scan
engine) builds the next plans on a host worker thread
(``data/plan_prefetch.py``) while the current dispatch runs.

With ``resident_selection=True`` (and ``method="pgm"``) the selection
rounds also stay on device: stage A runs as one jitted batch-scanned
pass over the engine's resident units — sharded over ``data`` when the
engine placed them on a mesh — via ``core/pgm.ResidentSelector``
instead of the sequential host-dispatched ``pgm_select`` path
(docs/DESIGN.md §1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import TrainConfig
from repro.core import baselines as bl
from repro.core.lastlayer import make_proj_for, units_gradients
from repro.core.metrics import overlap_index
from repro.core.pgm import ResidentSelector, Selection, pgm_select
from repro.data.pipeline import unit_durations
from repro.data.plan_prefetch import PlanPrefetcher
from repro.train import checkpoint as ckpt_mod
from repro.train import faults as faults_mod
from repro.train.engine import EpochEngine, make_engine, make_step_core
from repro.train.optim import NewbobState, make_update_for


@dataclasses.dataclass
class History:
    train_loss: List[float] = dataclasses.field(default_factory=list)
    val_loss: List[float] = dataclasses.field(default_factory=list)
    lr: List[float] = dataclasses.field(default_factory=list)
    selections: List[Dict] = dataclasses.field(default_factory=list)
    cost_units: float = 0.0        # full-epoch-equivalent compute units
    wall_time: float = 0.0
    final_params: Any = None
    skipped_steps: int = 0         # non-finite steps gated off on device
    rollbacks: int = 0             # divergence-watchdog restores
    preempted: bool = False        # exited early on SIGTERM/SIGINT
    # resident selection rounds: the kernel backend they ran on and how
    # many fell back to a soft-random subset (opt-in ladder only)
    selection_kernels: Optional[str] = None
    degraded_rounds: int = 0


def _max_consecutive(mask: np.ndarray) -> int:
    best = cur = 0
    for v in mask:
        cur = cur + 1 if v else 0
        best = max(best, cur)
    return best


def make_train_step(bundle, cfg: TrainConfig):
    return jax.jit(make_step_core(bundle, cfg))


def make_eval(bundle):
    @jax.jit
    def ev(params, batch):
        return bundle.per_example_loss(params, batch).mean()
    return ev


def _select(method, bundle, params, units, tc: TrainConfig, key, proj,
            val_units, durations, mesh=None, data_axis: str = "data",
            resident: Optional[ResidentSelector] = None):
    pc = tc.pgm
    n_units = jax.tree.leaves(units)[0].shape[0]
    budget = max(int(pc.subset_fraction * n_units), 1)
    if method == "pgm":
        if resident is not None:
            return resident(params, units, val_units=val_units)
        return pgm_select(bundle, params, units, pc, proj,
                          val_units=val_units, mesh=mesh, data_axis=data_axis)
    if method == "random":
        return bl.random_subset(key, n_units, budget)
    if method == "large_only":
        return bl.large_only(jnp.asarray(durations), budget)
    if method == "large_small":
        return bl.large_small(jnp.asarray(durations), budget)
    if method == "gradmatch_pb":
        g = units_gradients(bundle, params, units, proj,
                            exact=not pc.use_sketch)
        g_val = None
        if pc.val_matching:
            gv = units_gradients(bundle, params, val_units, proj,
                                 exact=not pc.use_sketch)
            g_val = gv.mean(axis=0) * float(n_units)
        return bl.gradmatch_pb(g, budget, pc.lam, pc.eps, pc.nonneg_weights,
                               g_val=g_val)
    raise ValueError(method)


def train_with_selection(
    bundle,
    units: Dict[str, np.ndarray],
    tc: TrainConfig,
    *,
    method: str = "pgm",            # pgm|random|large_only|large_small|
                                    # gradmatch_pb|full
    val_units=None,
    key=None,
    batch_units: int = 1,
    ckpt_dir: Optional[str] = None,
    resume: bool = False,
    engine: str = "scan",           # scan (device-resident) | host (legacy)
    resident_selection: bool = False,   # PGM stage A on the resident units
    mesh=None,                      # shard training + selection on a mesh
    data_axis: str = "data",
    spec_mode: str = "tp",          # SpecBuilder param-sharding policy
    epoch_chunk: int = 1,           # epochs folded into one scan dispatch
    plan_prefetch: bool = True,     # build next plans on a host thread
    fault_plan: Optional["faults_mod.FaultPlan"] = None,  # chaos harness
    log_fn: Callable[[str], None] = lambda s: None,
) -> History:
    eng = make_engine(engine, bundle, tc, units, val_units=val_units,
                      batch_units=batch_units, mesh=mesh,
                      data_axis=data_axis, spec_mode=spec_mode)
    # the engine may rebuild the bundle at construction (RNN-T
    # loss_vocab_chunk auto-tune); train and select on the tuned one
    bundle = getattr(eng, "bundle", bundle)
    is_scan = isinstance(eng, EpochEngine)
    key = jax.random.PRNGKey(tc.seed) if key is None else key
    params = bundle.init_params(key)
    opt_init, _ = make_update_for(tc)
    opt_state = opt_init(params)
    # bring the donated carry onto the mesh (identity without one)
    params, opt_state = eng.shard_state(params, opt_state)
    units_dev = eng.units
    val_dev = eng.val_units
    durations = unit_durations({k: np.asarray(v) for k, v in units.items()})
    proj = make_proj_for(bundle, jax.random.fold_in(key, 17),
                         tc.pgm.sketch_dim_h, tc.pgm.sketch_dim_v)
    # resident rounds: stage A is one jitted batch-scanned pass over the
    # device-resident units (data-sharded with a mesh); the selector
    # caches its executable (and the projections, closed over the jit)
    # across rounds
    resident = (ResidentSelector(bundle, tc.pgm, proj, mesh=mesh,
                                 data_axis=data_axis,
                                 on_failure=tc.pgm.on_failure,
                                 shard=getattr(eng, "act_shard", None),
                                 log_fn=log_fn)
                if resident_selection and method == "pgm" else None)

    hist = History()
    newbob = NewbobState(tc.lr)
    selection: Optional[Selection] = None
    start_epoch = 0
    mesh_shape = (dict(zip(mesh.axis_names, mesh.devices.shape))
                  if mesh is not None else None)
    # pod-axis compression: per-pod top-k error-feedback residuals ride
    # the checkpoint tree (key "err") so a resumed run continues from the
    # exact residuals, not fresh zeros (DESIGN.md §5)
    uses_err = getattr(eng, "uses_error_feedback", False)
    # pod-mode engines record their compressor in every manifest (also
    # for the stateless none/bf16 modes), so a resume under a different
    # mode is flagged and a same-mode resume stays silent
    pod_mode = getattr(eng, "pod_axis", None) is not None
    guard_on = bool(getattr(tc, "nonfinite_guard", False))

    def _ckpt_template_fn(manifest):
        # a checkpoint written without error-feedback state (different
        # compress_mode) must restore gracefully with fresh zero
        # residuals, not KeyError on a template leaf the archive never
        # had; shapes/dtypes only — restore replaces every leaf from the
        # archive, so don't allocate a device-resident zero tree
        tmpl = {"params": params, "opt": opt_state}
        if uses_err and any("'err'" in k for k in manifest["arrays"]):
            tmpl["err"] = jax.eval_shape(eng.init_compress_state, params)
        return tmpl

    def _restore_newest():
        """State from the newest checkpoint that passes checksum
        verification — a corrupt latest falls back to the previous
        intact step (DESIGN.md §10).  Returns
        ``(params, opt_state, newbob, selection, next_epoch)``."""
        loaded, manifest = ckpt_mod.restore_latest_intact(
            ckpt_dir, template_fn=_ckpt_template_fn,
            sharding_fn=eng.restore_sharding, log_fn=log_fn)
        p, o = loaded["params"], loaded["opt"]
        if uses_err:
            if "err" in loaded:
                eng.compress_state = loaded["err"]
            else:
                eng.compress_state = None
                log_fn("warning: no error-feedback state in checkpoint; "
                       "top-k residuals restart from zero")
        saved_cm = manifest.get("compress_mode")
        if (saved_cm or "none") != tc.compress_mode:
            log_fn(f"warning: checkpoint was written with compress_mode="
                   f"{saved_cm or 'none'!r}, resuming with "
                   f"{tc.compress_mode!r}")
        nb = NewbobState(manifest["extra"]["lr"],
                         manifest["extra"]["prev_loss"])
        sel = None
        if manifest["extra"].get("sel_indices") is not None:
            sel_idx = manifest["extra"]["sel_indices"]
            sel = Selection(
                jnp.asarray(sel_idx, jnp.int32),
                jnp.asarray(manifest["extra"]["sel_weights"], jnp.float32),
                jnp.asarray(sum(1 for i in sel_idx if i >= 0)),
                jnp.zeros((1,)))
        saved_mesh = manifest.get("mesh_shape")
        if saved_mesh != mesh_shape:
            log_fn(f"resharded checkpoint (saved mesh {saved_mesh} -> "
                   f"current {mesh_shape})")
        return p, o, nb, sel, manifest["extra"]["epoch"] + 1

    if resume and ckpt_dir and ckpt_mod.latest_step(ckpt_dir) is not None:
        params, opt_state, newbob, selection, start_epoch = _restore_newest()
        log_fn(f"resumed at epoch {start_epoch}")

    warm = tc.pgm.warm_start_epochs
    R = tc.pgm.select_every
    prefetcher = (PlanPrefetcher(max_pending=max(2, epoch_chunk))
                  if plan_prefetch and is_scan else None)
    sel_round = 0          # prefetch key component: one per selection
    writer = ckpt_mod.AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    preempt = faults_mod.PreemptionHandler(log_fn=log_fn).install()

    def _use_full(e: int) -> bool:
        return method == "full" or e < warm

    def _is_sel_epoch(e: int) -> bool:
        return not _use_full(e) and (e - warm) % R == 0

    def _plan_builder(e: int, sel: Optional[Selection]):
        if _use_full(e):
            base = lambda: eng.full_plan(e)
        else:
            idx, w = sel.indices, sel.weights
            base = lambda: eng.subset_plan(idx, w, e)
        if fault_plan is None:
            return base

        def build():
            fault_plan.maybe_fail_prefetch(e)
            return fault_plan.poison_plan(e, base())
        return build

    def _plan_key(e: int, rnd: int):
        # the watchdog re-keys plans by bumping the engine's plan_salt;
        # keys must carry it so stale pending plans never resolve
        salt = getattr(eng, "plan_salt", 0)
        return (("full", salt, e) if _use_full(e)
                else ("subset", salt, rnd, e))

    def _get_plan(e: int):
        build = _plan_builder(e, selection)
        if prefetcher is None:
            return build()
        return prefetcher.get(_plan_key(e, sel_round), build)

    t0 = time.time()
    try:
        epoch = start_epoch
        while epoch < tc.epochs:
            use_full = _use_full(epoch)
            # --- selection round (the host sync point) ---
            if not use_full and (selection is None or _is_sel_epoch(epoch)):
                sel_key = jax.random.fold_in(key, 1000 + epoch)
                new_sel = _select(method, bundle, params, units_dev, tc,
                                  sel_key, proj, val_dev, durations,
                                  mesh=mesh, data_axis=data_axis,
                                  resident=resident)
                oi = (overlap_index(np.asarray(selection.indices),
                                    np.asarray(new_sel.indices))
                      if selection is not None else float("nan"))
                selection = new_sel
                sel_round += 1
                if prefetcher is not None:
                    # keys change with the selection round: drop any
                    # pending plans so they can't pin buffer slots
                    prefetcher.invalidate()
                # selection cost: one grad-rep pass over all units ~ 1/3
                # epoch
                sel_cost = (1.0 / 3.0 if method in ("pgm", "gradmatch_pb")
                            else 0.0)
                hist.cost_units += sel_cost
                hist.selections.append({
                    "epoch": epoch,
                    "indices": np.asarray(selection.indices).tolist(),
                    "weights": np.asarray(selection.weights).tolist(),
                    "overlap_index": oi,
                })
                log_fn(f"epoch {epoch}: selected "
                       f"{int(selection.n_selected)} units (OI={oi:.3f})")

            # --- chunk of SGD epochs sharing this selection context ---
            if method == "full":
                boundary = tc.epochs
            elif epoch < warm:
                boundary = warm
            else:
                boundary = warm + ((epoch - warm) // R + 1) * R
            boundary = min(boundary, tc.epochs)
            chunk = (max(1, min(epoch_chunk, boundary - epoch))
                     if is_scan else 1)
            chunk_epochs = list(range(epoch, epoch + chunk))
            plans = [_get_plan(e) for e in chunk_epochs]
            # overlap the next dispatch: every later epoch whose selection
            # context is already decided (same selection, or a full plan)
            # can be built on the prefetch thread right now
            if prefetcher is not None:
                e_next = epoch + chunk
                while e_next < tc.epochs and not _is_sel_epoch(e_next):
                    if not prefetcher.schedule(
                            _plan_key(e_next, sel_round),
                            _plan_builder(e_next, selection)):
                        break
                    e_next += 1

            n_sel = (int(selection.n_selected)
                     if selection is not None else None)
            for p in plans:
                hist.cost_units += eng.epoch_cost(p, use_full=use_full,
                                                  n_selected=n_sel)
            if epoch_chunk == 1 or not is_scan:
                # per-epoch dispatch: validate + newbob on host (legacy
                # numerics — the parity-oracle path).  Keyed off the
                # *requested* chunk size, not this chunk's length, so a
                # chunked run uses one newbob implementation (the fp32
                # device one) everywhere — the anneal schedule stays a
                # pure function of the config even when boundaries leave
                # size-1 chunks
                params, opt_state, step_losses = eng.run_epoch(
                    params, opt_state, newbob.lr, plans[0])
                live = eng.plan_live_steps(plans[0])
                losses = np.asarray(step_losses, np.float64)[live]
                train_losses = [float(losses.mean()) if losses.size
                                else float("nan")]
                has_live = [losses.size > 0]
                if val_dev is not None:
                    vl = eng.validate(params)
                    newbob = newbob.update(vl, tc.anneal_factor,
                                           tc.improvement_threshold)
                else:
                    vl = float("nan")
                val_losses, lrs = [vl], [newbob.lr]
            else:
                # chunked dispatch: epochs, validations and newbob updates
                # all on device; one host fetch for the whole chunk
                (params, opt_state, step_losses, vls, lrs_dev, lr_out,
                 prev_out) = eng.run_epochs(params, opt_state, newbob.lr,
                                            newbob.prev_loss, plans)
                step_losses = np.asarray(step_losses, np.float64)
                train_losses = []
                has_live = []
                for i, p in enumerate(plans):
                    live = eng.plan_live_steps(p)
                    l = step_losses[i][live]
                    train_losses.append(float(l.mean()) if l.size
                                        else float("nan"))
                    has_live.append(l.size > 0)
                val_losses = [float(v) for v in np.asarray(vls)]
                lrs = [float(v) for v in np.asarray(lrs_dev)]
                newbob = NewbobState(float(lr_out), float(prev_out))

            # --- divergence watchdog (DESIGN.md §10) ---
            if guard_on:
                skm = (np.asarray(eng.last_skipped).reshape(-1) > 0.5
                       if eng.last_skipped is not None
                       else np.zeros(0, bool))
                n_sk = int(skm.sum())
                hist.skipped_steps += n_sk
                if n_sk:
                    log_fn(f"guard: skipped {n_sk} non-finite step(s) in "
                           f"epochs {chunk_epochs[0]}..{chunk_epochs[-1]}")
                bad_train = any(not np.isfinite(tl) for tl, h
                                in zip(train_losses, has_live) if h)
                bad_val = (val_dev is not None
                           and any(not np.isfinite(v) for v in val_losses))
                K = int(getattr(tc, "max_skipped_steps", 0) or 0)
                consec = _max_consecutive(skm)
                if (K > 0 and consec >= K) or bad_train or bad_val:
                    hist.rollbacks += 1
                    if hist.rollbacks > 3:
                        raise RuntimeError(
                            "divergence watchdog: giving up after 3 "
                            "rollbacks")
                    reason = (f"{consec} consecutive skipped steps"
                              if K > 0 and consec >= K
                              else "non-finite loss")
                    log_fn(f"watchdog: {reason} in epochs "
                           f"{chunk_epochs[0]}..{chunk_epochs[-1]}; "
                           f"rolling back with a re-keyed batch plan")
                    if writer is not None:
                        try:
                            writer.wait()
                        except BaseException as e:
                            log_fn(f"warning: async checkpoint write "
                                   f"failed: {e}")
                    eng.plan_salt = getattr(eng, "plan_salt", 0) + 1
                    sel_round += 1
                    if prefetcher is not None:
                        prefetcher.invalidate()
                    if (ckpt_dir
                            and ckpt_mod.latest_step(ckpt_dir) is not None):
                        (params, opt_state, newbob, selection,
                         epoch) = _restore_newest()
                        log_fn(f"watchdog: rolled back to epoch {epoch}")
                    else:
                        key = jax.random.fold_in(key,
                                                 7919 + hist.rollbacks)
                        params = bundle.init_params(key)
                        opt_state = opt_init(params)
                        params, opt_state = eng.shard_state(params,
                                                            opt_state)
                        if uses_err:
                            eng.compress_state = None
                        newbob = NewbobState(tc.lr)
                        selection = None
                        epoch = 0
                        log_fn("watchdog: no checkpoint; restarting from "
                               "re-initialised state")
                    continue

            for e, tl, vl, lr in zip(chunk_epochs, train_losses,
                                     val_losses, lrs):
                hist.train_loss.append(tl)
                hist.val_loss.append(vl)
                hist.lr.append(lr)
                log_fn(f"epoch {e}: train {tl:.4f} val {vl:.4f} "
                       f"lr {lr:.4f}")

            if fault_plan is not None:
                fault_plan.maybe_preempt(chunk_epochs[-1])
            preempted = preempt.triggered
            if ckpt_dir:
                extra = {"epoch": chunk_epochs[-1], "lr": newbob.lr,
                         "prev_loss": newbob.prev_loss,
                         "sel_indices": (np.asarray(
                             selection.indices).tolist()
                             if selection is not None else None),
                         "sel_weights": (np.asarray(
                             selection.weights).tolist()
                             if selection is not None else None)}
                if preempted:
                    extra["preempted"] = True
                tree = {"params": params, "opt": opt_state}
                if uses_err:
                    tree["err"] = (eng.compress_state
                                   if eng.compress_state is not None
                                   else eng.init_compress_state(params))
                writer.submit(chunk_epochs[-1], tree, extra,
                              mesh_shape=mesh_shape,
                              compress_mode=(tc.compress_mode if pod_mode
                                             else None))
            if preempted:
                if writer is not None:
                    writer.wait()
                hist.preempted = True
                log_fn(f"preemption: emergency checkpoint at epoch "
                       f"{chunk_epochs[-1]}; exiting resumably")
                break
            epoch += chunk
        if writer is not None:
            writer.wait()    # surface deferred write errors before returning
    finally:
        preempt.uninstall()
        if prefetcher is not None:
            prefetcher.close()
        if writer is not None:
            try:
                writer.close()
            except BaseException as e:
                log_fn(f"warning: checkpoint writer failed on close: {e}")

    hist.wall_time = time.time() - t0
    hist.final_params = params
    if resident is not None:
        hist.selection_kernels = resident.kernel_impl
        hist.degraded_rounds = resident.degraded_rounds
    return hist
