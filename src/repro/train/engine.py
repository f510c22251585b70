"""Device-resident scanned epoch engines for Algorithm 1's SGD phase.

Three execution paths live behind one engine interface (``make_engine``,
consumed by ``train/loop.py``):

  * ``HostEngine`` (``engine="host"``) — the legacy per-batch loop: one
    jit call per host-assembled batch, one eval call per validation
    unit.  Kept as the parity oracle.
  * ``EpochEngine`` (``engine="scan"``) — the whole corpus of selection
    units lives on device once; an epoch is a single jitted ``lax.scan``
    over a precomputed (seed, epoch)-keyed batch plan
    (``data/pipeline.epoch_plan`` / ``subset_epoch_plan``), with
    ``(params, opt_state)`` donated so the update runs in-place.
    Weighted-subset epochs are expressed as index+weight arrays gathered
    inside jit; validation is one vmapped call over the validation
    units.
  * ``EpochEngine`` with a ``mesh`` — the *same* scanned epoch compiled
    mesh-natively (DESIGN.md §5): the donated ``(params, opt_state)``
    carry is constrained to ``sharding/specs.py:SpecBuilder`` FSDP/TP
    partition specs, units/batches are sharded over the ``data`` axis,
    and GSPMD inserts the mean-psum of grads/metrics across ``data``
    that the per-shard loss terms require — one code path on 1 and N
    devices, parity-tested by ``tests/test_sharded_engine.py``.

Multi-epoch chunks: ``run_epochs`` folds several bucketed epochs into
one dispatch — an outer ``lax.scan`` over per-epoch plans whose body
runs the epoch, the vmapped validation, and the newbob lr update
entirely on device, so metrics come back to the host once per chunk and
selection rounds are the only host sync points.

Retrace-freedom (DESIGN.md §3): subset plans are padded with weight-0
padding rows (unit id ``-1``) up to a *bucketed* step count — the next
multiple of ``plan_granule`` (1/8 of the full-data step count) — so
selection rounds whose ``n_selected`` lands in the same bucket reuse one
compiled epoch executable, while a subset epoch still executes only
~``n_selected/n_units`` of the full-epoch steps (padding waste is
bounded by one granule, not by the subset fraction).  Padding rows are
bit-exact no-ops: the gather index is clamped, the step runs, and
``optim.gate_step`` selects the old ``(params, opt_state)`` leafwise, so
the padded scan's state matches the unpadded loop's exactly.
Retrace-freedom is asserted by ``tests/test_resident_selection.py`` /
``tests/test_sharded_engine.py`` through the shared compile-counter
contract (``repro.analysis.contracts.track_compiles``), which counts
actual XLA compilations rather than a per-function python side effect.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.data.pipeline import epoch_plan, subset_epoch_plan
from repro.train.compress import compressed_psum, init_error_state
from repro.train.optim import (clip_by_global_norm, gate_step,
                               make_update_for)


class PodSpec(NamedTuple):
    """Static description of the two-level ``data x pod`` step
    (DESIGN.md §5): which mesh axis is the slow cross-pod dimension, how
    many pods it has, and which ``train/compress.py`` compressor runs on
    its gradient collective."""

    axis: str          # mesh axis name of the slow cross-pod dimension
    n_pods: int
    mode: str          # none | bf16 | topk (compressed_psum mode)
    k_frac: float      # top-k fraction per leaf (mode == "topk")
    data_axis: str     # fast intra-pod data axis (dense GSPMD psum)
    mesh: Any


def make_step_core(bundle, cfg: TrainConfig, shard=None, pod=None):
    """The un-jitted per-batch SGD update shared by the legacy host loop
    (which jits it per call) and the scanned engines (which embed it in
    the scan body).

    ``step_on`` (optional traced bool scalar) is the padding-batch gate:
    when False the optimizer update is a bit-exact no-op and every metric
    is zeroed (no state advance, no metric contribution); when ``None``
    (host loop — plans it consumes are never padded) no gating ops are
    emitted.

    The loss closure is whatever ``bundle.loss_fn`` resolves to from the
    model config — for RNN-T that is the fused custom_vjp transducer
    loss by default (``cfg.rnnt.loss_impl``, DESIGN.md §2), so the
    scanned epoch's ``value_and_grad`` runs the analytic alpha/beta
    backward with no ``(B, T, U+1, V)`` joint tensor and no per-scan-step
    autodiff residuals; ``loss_impl="dense"`` rebuilds every engine on
    the materialized-joint oracle for parity runs.

    ``shard`` (optional ``Sharder``) is forwarded into the loss for
    activation-sharding constraints; when ``None`` the emitted jaxpr is
    identical to the pre-sharder engine.

    ``pod`` (optional :class:`PodSpec`) switches the step to the
    two-level ``data x pod`` form (DESIGN.md §5): the batch's example
    axis is split into ``n_pods`` equal slices, each pod takes
    ``value_and_grad`` of its *local* weighted loss (rescaled so the pod
    mean of objectives equals the global weighted mean — the loss
    denominator is the weight sum, so per-pod means don't average to the
    global mean without the ``W_k / W`` factor), and the per-pod
    gradients meet in an explicit
    ``train/compress.py:compressed_psum`` over the pod axis — bound here
    by a ``vmap(axis_name=pod.axis, spmd_axis_name=pod.axis)``, which
    GSPMD lowers to a real cross-pod all-reduce while the intra-pod
    example reduction stays a dense GSPMD mean-psum over ``data``.  The
    pod step's signature gains the per-pod error-feedback state:
    ``step(params, opt_state, batch, lr, err, step_on) ->
    (params, opt_state, metrics, err)``; on gated-off padding steps the
    error state is returned bit-identically (``optim.gate_step``).

    Aux losses (e.g. the MoE router load-balance penalty) are computed
    per pod and pod-averaged — the standard data-parallel approximation
    (each replica balances its local sub-batch).  For aux-free families
    (dense LMs, RNN-T) this is exact and ``mode="none"`` stays bit-close
    to the one-level engines; for MoE the load-balance term is nonlinear
    in batch composition, so per-pod aux is a deliberate semantic choice,
    not a parity-preserving identity.

    Non-finite guard (``cfg.nonfinite_guard``, DESIGN.md §10): the step
    additionally checks loss and (clipped) gradients for NaN/Inf in-jit
    and folds the result into the ``step_on`` gate — a poisoned batch
    becomes a bit-exact no-op exactly like a weight-0 padding row (same
    ``gate_step`` select, composing with pod-mode error-feedback
    gating), its metrics are zeroed, and ``metrics["skipped"]`` reports
    whether a *live* step was suppressed.  The check is trace-static:
    guard on/off never retraces within a run, and a guarded run on
    all-finite data is bitwise identical to an unguarded one (the gate
    selects the new state everywhere).
    """
    _, opt_update = make_update_for(cfg)
    guard = bool(getattr(cfg, "nonfinite_guard", False))

    if pod is None:
        def step(params, opt_state, batch, lr, step_on=None):
            def loss(p):
                if shard is None:
                    total, metrics = bundle.loss_fn(p, batch)
                else:
                    total, metrics = bundle.loss_fn(p, batch, shard=shard)
                return total, metrics

            (l, metrics), grads = jax.value_and_grad(loss,
                                                     has_aux=True)(params)
            grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
            if guard:
                # the clip already paid for the global norm: any NaN/Inf
                # in the raw grads poisons the sum-of-squares, so one
                # scalar isfinite replaces a leafwise tree sweep (a
                # finite tree whose norm *overflows* is also gated off —
                # its clip scale would be 0, a degenerate step)
                finite = jnp.isfinite(l) & jnp.isfinite(gnorm)
                ok = finite if step_on is None else step_on & finite
            else:
                ok = step_on
            params, opt_state = opt_update(params, grads, opt_state, lr,
                                           step_on=ok)
            metrics = dict(metrics, grad_norm=gnorm)
            if ok is not None:
                metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                           for k, v in metrics.items()}
            if guard:
                live = jnp.bool_(True) if step_on is None else step_on
                metrics["skipped"] = live & ~finite
            return params, opt_state, metrics

        return step

    data_size = pod.mesh.shape[pod.data_axis]

    def split_pods(v):
        """(E, ...) -> (n_pods, E/n_pods, ...) constrained P(pod, data)."""
        v = v.reshape((pod.n_pods, v.shape[0] // pod.n_pods) + v.shape[1:])
        ax = pod.data_axis if v.shape[1] % data_size == 0 else None
        return jax.lax.with_sharding_constraint(
            v, NamedSharding(pod.mesh,
                             P(pod.axis, ax, *([None] * (v.ndim - 2)))))

    def pod_step(params, opt_state, batch, lr, err, step_on=None):
        bp = {k: split_pods(v) for k, v in batch.items()}

        def per_pod(b_k, e_k):
            w = b_k.get("weights")
            W_k = (jnp.sum(w.astype(jnp.float32)) if w is not None
                   else jnp.float32(jax.tree.leaves(b_k)[0].shape[0]))
            # global weight sum / n_pods: the tiny scalar collective that
            # turns per-pod weighted means into the global weighted mean
            W = jax.lax.pmean(W_k, pod.axis)
            wr = W_k / jnp.maximum(W, 1e-9)

            def obj(p):
                if shard is None:
                    total, m = bundle.loss_fn(p, b_k)
                else:
                    total, m = bundle.loss_fn(p, b_k, shard=shard)
                return m["loss"] * wr + m.get("aux_loss", 0.0), m

            (_, m), grads = jax.value_and_grad(obj, has_aux=True)(params)
            grads, e_new = compressed_psum(grads, pod.axis, pod.mode,
                                           err=e_k, k_frac=pod.k_frac)
            metrics = {k: jax.lax.pmean(v, pod.axis) for k, v in m.items()}
            metrics["loss"] = jax.lax.pmean(m["loss"] * wr, pod.axis)
            if "total_loss" in m:
                metrics["total_loss"] = (metrics["loss"]
                                         + metrics.get("aux_loss", 0.0))
            return grads, e_new, metrics

        # the pmean over the *complete* pod axis leaves grads/metrics
        # unbatched (out_axes=None): only the error state stays per-pod
        grads, new_err, metrics = jax.vmap(
            per_pod, in_axes=(0, 0), out_axes=(None, 0, None),
            axis_name=pod.axis, spmd_axis_name=pod.axis)(bp, err)
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
        if guard:
            # the check runs on the post-collective gradients: a NaN/Inf
            # in any pod poisons the psum, so every pod gates off the
            # same step (and rolls its error-feedback residuals back)
            finite = jnp.isfinite(metrics["loss"]) & jnp.isfinite(gnorm)
            ok = finite if step_on is None else step_on & finite
        else:
            ok = step_on
        params, opt_state = opt_update(params, grads, opt_state, lr,
                                       step_on=ok)
        metrics = dict(metrics, grad_norm=gnorm)
        if ok is not None:
            # padding/guarded batches advance nothing: the error-feedback
            # state is selected back bit-exactly, like params/opt_state
            new_err = gate_step(ok, new_err, err)
            metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                       for k, v in metrics.items()}
        if guard:
            live = jnp.bool_(True) if step_on is None else step_on
            metrics["skipped"] = live & ~finite
        return params, opt_state, metrics, new_err

    return pod_step


def newbob_step(lr, prev_loss, val_loss, anneal_factor, threshold):
    """Device-side newbob update (the traced twin of
    ``optim.NewbobState.update``): anneal ``lr`` by ``anneal_factor``
    when the relative validation improvement over ``prev_loss`` drops
    below ``threshold``.  ``prev_loss = inf`` (first epoch) and a NaN
    ``val_loss`` (no validation set) both leave ``lr`` untouched, like
    the host version."""
    rel = (prev_loss - val_loss) / jnp.maximum(jnp.abs(prev_loss), 1e-9)
    anneal = (prev_loss != jnp.inf) & (rel < threshold)
    return jnp.where(anneal, lr * anneal_factor, lr), val_loss


def plan_live_steps(plan) -> np.ndarray:
    """Host-side mask of real (non-padding) steps in a plan — use it to
    exclude padding rows from per-step metric aggregates."""
    return np.asarray(plan[0])[:, 0] >= 0


def autotune_loss_vocab_chunk(bundle, units, batch_units: int):
    """Resolve ``RNNTConfig.loss_vocab_chunk == 0`` ("auto") into a
    concrete chunk width at engine build time and rebuild the bundle on
    it when that changes the layout.

    The fused transducer loss streams a ``(rows, chunk)`` slab per vocab
    chunk — the joint-head columns plus the per-chunk lattice block,
    ``rows ~= B * (U+1) + joint_dim`` for batch size
    ``B = batch_units * unit_size`` — so the width comes from the shared
    ``core/chunking.py:auto_vocab_chunk`` resolver (the same budget that
    tiles the grad-sketch kernel's vocab axis).  Small/smoke vocabs
    resolve to a single full-vocab chunk, i.e. exactly the historical
    ``0`` behaviour; an explicit negative value keeps forcing one chunk,
    and an explicit positive value is always respected.

    Returns ``(bundle, resolved_chunk)``; the bundle is rebuilt (same
    config surgery as ``models/api.py:build_model``) only when the tuned
    width is smaller than the vocab.
    """
    cfg_m = bundle.cfg
    r = getattr(cfg_m, "rnnt", None)
    if getattr(cfg_m, "family", None) != "rnnt" or r is None:
        return bundle, None
    if r.loss_vocab_chunk != 0:
        return bundle, r.loss_vocab_chunk
    leaf = jax.tree.leaves(units)[0]
    unit_size = int(leaf.shape[1])
    U = int(units["tokens"].shape[2])
    from repro.core.chunking import auto_vocab_chunk
    rows = int(batch_units) * unit_size * (U + 1) + int(r.joint_dim)
    tuned = auto_vocab_chunk(rows, int(r.vocab_size))
    if tuned >= int(r.vocab_size):
        return bundle, tuned
    import dataclasses

    from repro.models.api import build_model
    cfg_new = dataclasses.replace(
        cfg_m, rnnt=dataclasses.replace(r, loss_vocab_chunk=tuned))
    return build_model(cfg_new), tuned


class EpochEngine:
    """Scanned-epoch executor around a ModelBundle.

    Residency: ``units`` (and optional ``val_units``) are moved to device
    once at construction and never leave — SGD epochs gather batches from
    them inside jit, and PGM stage A can sketch them in place via
    ``core/pgm.ResidentSelector`` (no host round-trip per selection
    round).

    Mesh (DESIGN.md §5): with ``mesh`` the engine owns placement and
    compilation for N devices — units and validation units are
    ``device_put`` sharded over ``data_axis`` along their leading
    ``n_units`` dim (when divisible), the donated ``(params, opt_state)``
    carry is constrained to ``SpecBuilder`` FSDP/TP partition specs
    (``spec_mode`` selects the policy), gathered batches are constrained
    to shard their example axis over ``data``, and plan arrays shard
    their ``batch_units`` axis over ``data``.  GSPMD then partitions the
    step: per-shard loss/grad terms are combined with a mean-psum over
    ``data``, exactly the collective an explicit
    ``train/compress.py:compressed_psum`` emits on the slow ``pod`` axis
    of a multi-pod mesh.  Callers bring the carry onto the mesh with
    ``shard_state`` (fresh init) or ``restore_sharding`` (checkpoint
    restore).  Without a mesh the emitted jaxpr is identical to the
    single-device engine.

    Two-level ``data x pod`` mode (DESIGN.md §5): when the mesh carries
    ``cfg.pod_axis``, the scan body computes per-pod gradients (gathered
    batches place their example axis over ``(pod, data)`` jointly; units
    stay data-sharded/pod-replicated) and runs
    an explicit ``train/compress.py:compressed_psum`` —
    ``cfg.compress_mode`` ``none`` / ``bf16`` / ``topk`` — over the slow
    pod axis, while the intra-pod example reduction stays a dense GSPMD
    mean-psum over ``data``.  Params (and the mirrored optimizer state)
    keep FSDP specs over ``data`` only — replicated across pods, the
    standard multi-pod layout.  Top-k error-feedback residuals live in
    ``compress_state``: per-pod leaves ``(n_pods, *param_shape)`` sharded
    ``P(pod, *param_fsdp_spec)``, donated into every dispatch as part of
    the scan carry, advanced not-at-all on weight-0 padding steps
    (``optim.gate_step``), and checkpointed next to (params, opt_state)
    so resume is bit-exact (``train/loop.py``).

    Plans: ``full_plan`` / ``subset_plan`` return ``(batch_idx, batch_w)``
    index/weight arrays of shape ``(n_steps, batch_units)``.  Both are
    pure functions of ``(seed, epoch)`` (resume rebuilds them exactly —
    which also makes them safe to build ahead of time on a prefetch
    thread, see ``data/plan_prefetch.py``).  Full plans always have
    ``steps_per_epoch_max = n_units // batch_units`` steps; subset plans
    are padded with id ``-1`` / weight ``0`` rows up to
    ``bucket_steps(live)`` — the next multiple of ``plan_granule`` — so
    rounds with a stable selection budget reuse one epoch executable
    regardless of the exact ``n_selected``, at a padding overhead of at
    most one granule (1/8 epoch).

    Donation contract: inputs to ``run_epoch`` / ``run_epochs`` are
    donated — the caller must treat the passed-in ``params`` /
    ``opt_state`` buffers as consumed and continue with the returned
    values (the scan carry aliases them in place).
    """

    kind = "scan"

    def __init__(self, bundle, cfg: TrainConfig,
                 units: Dict[str, Any],
                 val_units: Optional[Dict[str, Any]] = None,
                 batch_units: int = 1,
                 mesh=None, data_axis: str = "data",
                 spec_mode: str = "tp"):
        bundle, self.loss_vocab_chunk = autotune_loss_vocab_chunk(
            bundle, units, batch_units)
        self.bundle = bundle
        self.cfg = cfg
        self.batch_units = int(batch_units)
        self.mesh = mesh
        self.data_axis = data_axis
        # two-level data x pod mode (DESIGN.md §5): active whenever the
        # mesh carries the configured pod axis — the step then computes
        # per-pod gradients and runs compressed_psum over that axis
        # inside the epoch scan
        pod_active = (mesh is not None
                      and cfg.pod_axis in getattr(mesh, "axis_names", ()))
        if cfg.compress_mode != "none" and not pod_active:
            raise ValueError(
                f"compress_mode={cfg.compress_mode!r} needs a mesh with a "
                f"{cfg.pod_axis!r} axis (e.g. --mesh 2x2 with axes "
                f"data x pod); got mesh="
                f"{None if mesh is None else tuple(mesh.axis_names)}")
        self.pod_axis = cfg.pod_axis if pod_active else None
        self.n_pods = int(mesh.shape[cfg.pod_axis]) if pod_active else 0
        self._pod = (PodSpec(cfg.pod_axis, self.n_pods, cfg.compress_mode,
                             cfg.compress_k_frac, data_axis, mesh)
                     if pod_active else None)
        #: per-pod top-k error-feedback residuals (None until the first
        #: topk epoch or a checkpoint restore; donated into every run)
        self.compress_state: Optional[Any] = None
        if mesh is not None:
            from repro.sharding.specs import SpecBuilder
            self.spec: Optional[Any] = SpecBuilder(
                mesh, mode=spec_mode, pod_axis=self.pod_axis,
                arch=getattr(bundle.cfg, "name", None))
        else:
            self.spec = None
        # RNN-T on a mesh: hand the loss a MeshSharder so the fused
        # transducer loss can pin its joint-factor boundary ("act_bsd")
        # — free GSPMD propagation through the CRDNN encoder produces
        # *wrong values* on XLA:CPU SPMD without the anchor (LM stacks
        # carry their own in-model annotations and stay sharder-free
        # here to keep their jaxprs unchanged).  Pod mode anchors every
        # family: the per-pod vmap prepends the pod axis to each act_bsd
        # spec (spmd_axis_name), and without the anchor the partitioner
        # falls back to full rematerialization of the layer-scan carry.
        # Expert mode anchors too: the (E, G, C, d) dispatch boundary
        # must pin its E dim to the expert axis for the all-to-all to
        # materialize instead of a full expert-bank gather
        if mesh is not None and (bundle.cfg.family == "rnnt"
                                 or pod_active or spec_mode == "expert"):
            from repro.sharding.specs import MeshSharder
            self.act_shard: Optional[Any] = MeshSharder(
                mesh, mode=spec_mode, pod_axis=self.pod_axis,
                arch=getattr(bundle.cfg, "name", None))
        else:
            self.act_shard = None
        self.units = self._place_units(units)
        self.val_units = (None if val_units is None
                          else self._place_units(val_units))
        self.n_units = int(jax.tree.leaves(self.units)[0].shape[0])
        self.unit_size = int(jax.tree.leaves(self.units)[0].shape[1])
        #: full-data step count (upper bound for every plan shape)
        self.steps_per_epoch_max = self.n_units // self.batch_units
        #: bucket granule for padded subset plans (1/8 of a full epoch)
        self.plan_granule = max(self.steps_per_epoch_max // 8, 1)
        #: non-finite step guard (DESIGN.md §10): trace-static, so the
        #: guarded engine compiles once like the unguarded one
        self.guard = bool(getattr(cfg, "nonfinite_guard", False))
        #: plan re-keying salt: the divergence watchdog bumps this on
        #: rollback so the replayed epochs draw a fresh batch order
        #: (plans stay pure functions of (seed, salt, epoch))
        self.plan_salt = 0
        #: per-step skip mask (device array) / total skip count of the
        #: last run_epoch/run_epochs dispatch; None when the guard is off
        self.last_skipped: Optional[jax.Array] = None
        self.last_n_skipped: Optional[jax.Array] = None
        if self._pod is not None and \
                (self.batch_units * self.unit_size) % self.n_pods:
            raise ValueError(
                f"batch ({self.batch_units} units x {self.unit_size} "
                f"examples) must divide into n_pods={self.n_pods} equal "
                f"per-pod slices")
        step_core = make_step_core(bundle, cfg, shard=self.act_shard,
                                   pod=self._pod)
        unit_size = self.unit_size
        pod = self._pod
        guard = self.guard

        def make_body(lr, units):
            def body(carry, xs):
                if guard:
                    *carry, nsk = carry
                if pod is None:
                    p, s = carry
                else:
                    p, s, err = carry
                idx, w = xs
                # plan rows are wholly real or wholly padding; padding
                # rows carry id -1 / weight 0 and must be bit-exact no-ops
                live = idx[0] >= 0
                batch = self.gather_batch(units, idx)
                if "weights" in batch:
                    batch = dict(batch, weights=batch["weights"]
                                 * jnp.repeat(w, unit_size))
                if pod is None:
                    p, s, metrics = step_core(p, s, batch, lr, step_on=live)
                    carry = (p, s)
                else:
                    p, s, metrics, err = step_core(p, s, batch, lr, err,
                                                   step_on=live)
                    carry = (p, s, err)
                if not guard:
                    return carry, metrics["loss"]
                # the skipped-step counter rides the donated carry; the
                # per-step mask joins the ys so the host watchdog can see
                # *consecutive* skips without an extra sync
                sk = metrics["skipped"]
                nsk = nsk + sk.astype(jnp.int32)
                return carry + (nsk,), (metrics["loss"],
                                        sk.astype(jnp.float32))

            return body

        def scan_epoch(carry, lr, xs, units):
            """One epoch scan; normalizes the guard-on/-off carry and ys
            shapes to ``(state_carry, losses, skipped, n_skipped)`` with
            ``skipped/n_skipped = None`` when the guard is off."""
            if not guard:
                carry, losses = jax.lax.scan(make_body(lr, units), carry,
                                             xs)
                return carry, losses, None, None
            (*carry, nsk), (losses, skipped) = jax.lax.scan(
                make_body(lr, units),
                tuple(carry) + (jnp.zeros((), jnp.int32),), xs)
            return tuple(carry), losses, skipped, nsk

        # the resident units are an argument of every dispatch, never a
        # closed-over constant: a closure would embed the whole corpus
        # in the executable, unsharded
        if pod is None:
            def run(params, opt_state, batch_idx, batch_w, lr, units):
                params, opt_state = self._constrain_state(params, opt_state)
                (params, opt_state), losses, skipped, nsk = scan_epoch(
                    (params, opt_state), lr, (batch_idx, batch_w), units)
                return params, opt_state, losses, skipped, nsk

            # donate (params, opt_state): the scan carry re-uses their
            # buffers
            self._run = jax.jit(run, donate_argnums=(0, 1))
        else:
            def run(params, opt_state, err, batch_idx, batch_w, lr, units):
                params, opt_state = self._constrain_state(params, opt_state)
                err = self._constrain_err(err)
                (params, opt_state, err), losses, skipped, nsk = scan_epoch(
                    (params, opt_state, err), lr, (batch_idx, batch_w),
                    units)
                return params, opt_state, err, losses, skipped, nsk

            # the per-pod error-feedback residuals join the donated carry
            self._run = jax.jit(run, donate_argnums=(0, 1, 2))

        act_shard = self.act_shard

        def val_mean(params, val_dev):
            # validation gets the same activation anchor as the training
            # step (the fused RNN-T loss needs it on a mesh; identity
            # jaxpr when no sharder)
            def unit_loss(u):
                if act_shard is None:
                    return bundle.per_example_loss(params, u).mean()
                return bundle.per_example_loss(params, u,
                                               shard=act_shard).mean()

            return jax.vmap(unit_loss)(val_dev).mean()

        self._validate = jax.jit(val_mean)

        def chunk_epoch_body(state_carry, val_dev, lr_c, prev, xs, units):
            """Shared inner body of the chunked dispatch: one epoch scan
            + validation + newbob.  Returns the updated state carry, lr,
            prev, the epoch skip count, and this epoch's ys (losses
            [, skip mask], val loss, lr)."""
            state_carry, losses, skipped, nsk = scan_epoch(state_carry,
                                                           lr_c, xs, units)
            p = state_carry[0]
            if val_dev is not None:
                vl = val_mean(p, val_dev)
                lr_n, prev = newbob_step(
                    lr_c, prev, vl, cfg.anneal_factor,
                    cfg.improvement_threshold)
            else:
                vl = jnp.float32(jnp.nan)
                lr_n = lr_c
            ys = ((losses, vl, lr_n) if not guard
                  else (losses, skipped, vl, lr_n))
            return state_carry, lr_n, prev, nsk, ys

        if pod is None:
            def run_chunk(params, opt_state, val_dev, batch_idx, batch_w,
                          lr, prev_loss, units):
                """batch_idx/batch_w: (n_epochs, n_steps, batch_units).
                The whole chunk — epochs, validations, newbob updates —
                is one dispatch; metrics are accumulated in the scan ys
                and fetched once by the caller."""
                params, opt_state = self._constrain_state(params, opt_state)

                def epoch(carry, xs):
                    if guard:
                        p, s, lr_c, prev, nsk = carry
                    else:
                        p, s, lr_c, prev = carry
                    (p, s), lr_n, prev, nsk_e, ys = chunk_epoch_body(
                        (p, s), val_dev, lr_c, prev, xs, units)
                    if guard:
                        return (p, s, lr_n, prev, nsk + nsk_e), ys
                    return (p, s, lr_n, prev), ys

                carry0 = (params, opt_state, lr, prev_loss)
                if guard:
                    carry0 = carry0 + (jnp.zeros((), jnp.int32),)
                carry, ys = jax.lax.scan(epoch, carry0,
                                         (batch_idx, batch_w))
                if guard:
                    params, opt_state, lr, prev_loss, nsk = carry
                    losses, skipped, vls, lrs = ys
                else:
                    params, opt_state, lr, prev_loss = carry
                    (losses, vls, lrs), skipped, nsk = ys, None, None
                return (params, opt_state, losses, skipped, nsk, vls, lrs,
                        lr, prev_loss)

            self._run_chunk = jax.jit(run_chunk, donate_argnums=(0, 1))
        else:
            def run_chunk(params, opt_state, err, val_dev, batch_idx,
                          batch_w, lr, prev_loss, units):
                """Pod-mode chunk: identical dispatch shape, with the
                per-pod error-feedback residuals threaded through the
                outer epoch carry next to (params, opt_state)."""
                params, opt_state = self._constrain_state(params, opt_state)
                err = self._constrain_err(err)

                def epoch(carry, xs):
                    if guard:
                        p, s, e, lr_c, prev, nsk = carry
                    else:
                        p, s, e, lr_c, prev = carry
                    (p, s, e), lr_n, prev, nsk_e, ys = chunk_epoch_body(
                        (p, s, e), val_dev, lr_c, prev, xs, units)
                    if guard:
                        return (p, s, e, lr_n, prev, nsk + nsk_e), ys
                    return (p, s, e, lr_n, prev), ys

                carry0 = (params, opt_state, err, lr, prev_loss)
                if guard:
                    carry0 = carry0 + (jnp.zeros((), jnp.int32),)
                carry, ys = jax.lax.scan(epoch, carry0,
                                         (batch_idx, batch_w))
                if guard:
                    params, opt_state, err, lr, prev_loss, nsk = carry
                    losses, skipped, vls, lrs = ys
                else:
                    params, opt_state, err, lr, prev_loss = carry
                    (losses, vls, lrs), skipped, nsk = ys, None, None
                return (params, opt_state, err, losses, skipped, nsk, vls,
                        lrs, lr, prev_loss)

            self._run_chunk = jax.jit(run_chunk, donate_argnums=(0, 1, 2))

    # -- mesh placement helpers ----------------------------------------
    def _place_units(self, units):
        # units stay sharded over `data` only, replicated across pods —
        # combined (pod, data) placement makes the in-scan unit gather
        # (and the vmapped validation) fall into XLA:SPMD full-remat
        # fallbacks on the host backend; the per-pod compute split
        # happens on the *gathered batch* instead (_constrain_batch +
        # make_step_core.split_pods)
        place = _data_sharded_put(self.mesh, self.data_axis)
        return {k: place(jnp.asarray(v)) for k, v in units.items()}

    def gather_batch(self, units, idx):
        """The step's batch for one plan row ``idx`` (padding ids -1 read
        unit 0 and are gated off by weight), example axis data-sharded
        on a mesh."""
        gidx = jnp.maximum(idx, 0)
        batch = {k: v[gidx].reshape((-1,) + v.shape[2:])
                 for k, v in units.items()}
        return self._constrain_batch(batch)

    def _constrain_batch(self, batch):
        """Shard the gathered batch's example axis over ``data`` (when
        divisible) — the step's per-shard loss/grad terms then reduce
        with a GSPMD mean-psum across the axis.  In pod mode the example
        axis spans ``(pod, data)`` jointly; the pod step then splits it
        into per-pod slices (``make_step_core``) without moving data."""
        if self.mesh is None:
            return batch
        if self._pod is not None:
            axes_t: Tuple[str, ...] = (self.pod_axis, self.data_axis)
        else:
            axes_t = (self.data_axis,)
        size = int(np.prod([self.mesh.shape[a] for a in axes_t]))
        spec_ax = axes_t if len(axes_t) > 1 else axes_t[0]

        def con(v):
            ax = spec_ax if v.shape[0] % size == 0 else None
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh,
                                 P(ax, *([None] * (v.ndim - 1)))))

        return {k: con(v) for k, v in batch.items()}

    def _constrain_state(self, params, opt_state):
        """Pin the donated carry to the SpecBuilder FSDP/TP specs so the
        whole scan (and its outputs, via donation) keeps them."""
        if self.mesh is None:
            return params, opt_state
        con = lambda t: jax.lax.with_sharding_constraint(
            t, self.state_shardings(t))
        return con(params), con(opt_state)

    def state_shardings(self, tree):
        """NamedShardings for a params-shaped tree (optimizer states
        mirror the params tree, so the same key-path rules apply)."""
        return self.spec.to_shardings(self.spec.param_specs(tree))

    def err_shardings(self, tree):
        """NamedShardings for the per-pod error-feedback state: each leaf
        mirrors a param with a leading ``n_pods`` dim, so its spec is
        ``P(pod, *param_fsdp_spec)`` — pod-local residuals, FSDP-sliced
        like the param they track."""
        flat, tdef = jax.tree_util.tree_flatten_with_path(tree)
        shs = [NamedSharding(self.mesh, P(
            self.pod_axis,
            *self.spec.param_spec(jax.tree_util.keystr(p), l.shape[1:])))
            for p, l in flat]
        return jax.tree_util.tree_unflatten(tdef, shs)

    def _constrain_err(self, err):
        if err is None or self.mesh is None:
            return err
        return jax.tree.map(jax.lax.with_sharding_constraint, err,
                            self.err_shardings(err))

    # -- compression state ---------------------------------------------
    @property
    def uses_error_feedback(self) -> bool:
        """True when the engine carries per-pod top-k residuals that must
        be checkpointed next to (params, opt_state) for exact resume."""
        return self._pod is not None and self._pod.mode == "topk"

    def init_compress_state(self, params):
        """Fresh zero error-feedback state, pod-sharded on the mesh;
        None unless the engine compresses with error feedback."""
        if not self.uses_error_feedback:
            return None
        err = init_error_state(params, n_pods=self.n_pods)
        return jax.device_put(err, self.err_shardings(err))

    def _ensure_compress_state(self, params):
        if self.uses_error_feedback and self.compress_state is None:
            self.compress_state = self.init_compress_state(params)
        return self.compress_state

    def shard_state(self, params, opt_state):
        """Bring a freshly-initialized carry onto the mesh with the
        engine's FSDP/TP shardings (identity without a mesh)."""
        if self.mesh is None:
            return params, opt_state
        return (jax.device_put(params, self.state_shardings(params)),
                jax.device_put(opt_state, self.state_shardings(opt_state)))

    def restore_sharding(self, path: str, arr):
        """``checkpoint.restore(sharding_fn=...)`` hook: reshard a
        restored leaf onto this engine's mesh — elastic restore across
        mesh shapes (DESIGN.md §5).  Returns None without a mesh.
        Error-feedback leaves (checkpoint key ``err``) carry a leading
        pod dim and reshard to ``P(pod, *param_spec)``."""
        if self.mesh is None:
            return None
        if self._pod is not None and "['err']" in path:
            return NamedSharding(self.mesh, P(
                self.pod_axis,
                *self.spec.param_spec(path, tuple(np.shape(arr))[1:])))
        return NamedSharding(self.mesh,
                             self.spec.param_spec(path, np.shape(arr)))

    def _put_plan(self, idx, w):
        idx, w = jnp.asarray(idx), jnp.asarray(w)
        if self.mesh is not None and \
                idx.shape[-1] % self.mesh.shape[self.data_axis] == 0:
            spec = P(*([None] * (idx.ndim - 1)), self.data_axis)
            sh = NamedSharding(self.mesh, spec)
            idx, w = jax.device_put(idx, sh), jax.device_put(w, sh)
        return idx, w

    # ------------------------------------------------------------------
    def _plan_seed(self) -> int:
        """Plan seed including the watchdog's re-key salt: 0 rollbacks
        leave it exactly ``cfg.seed`` (bit-identical schedules); each
        rollback shifts every subsequent epoch's batch order so a replay
        doesn't march through the same poisoned sequence."""
        return self.cfg.seed + 1_000_003 * self.plan_salt

    def full_plan(self, epoch: int) -> Tuple[jax.Array, jax.Array]:
        """(seed, epoch)-keyed full-data plan; unit weights are 1.  Shape
        ``(steps_per_epoch_max, batch_units)`` — identical to padded
        subset plans, so full and subset epochs share one executable."""
        idx = epoch_plan(self.n_units, self._plan_seed(), epoch,
                         self.batch_units)
        return self._put_plan(idx, np.ones(idx.shape, np.float32))

    def bucket_steps(self, n_live_steps: int) -> int:
        """Round a live step count up to the next ``plan_granule``
        multiple (capped at ``steps_per_epoch_max``): the padded-plan
        shape that bounds both recompiles (≤8 distinct buckets ever; one
        in the common stable-budget case) and padding waste (≤1
        granule).  Never returns 0 — a selection with fewer live units
        than a batch still yields a one-granule all-padding plan, keeping
        the shape inside the bucket family instead of tracing a fresh
        zero-length executable."""
        g = self.plan_granule
        return min(max(-(-n_live_steps // g) * g, g),
                   self.steps_per_epoch_max)

    def subset_plan(self, indices, weights, epoch: int,
                    pad_to_steps: Optional[int] = None,
                    ) -> Tuple[jax.Array, jax.Array]:
        """(seed, epoch)-keyed weighted-subset plan.

        By default the plan is padded with weight-0 rows to
        ``bucket_steps(live)`` so changing ``n_selected`` between
        selection rounds reuses the compiled epoch executable while a
        subset epoch still runs only ~``n_selected`` steps' worth of
        compute (pass ``pad_to_steps=0`` for the legacy unpadded shape,
        or any explicit step count)."""
        if pad_to_steps is None:
            n_live = int((np.asarray(indices) >= 0).sum())
            pad_to_steps = self.bucket_steps(n_live // self.batch_units)
        idx, w = subset_epoch_plan(np.asarray(indices), np.asarray(weights),
                                   self._plan_seed(), epoch,
                                   self.batch_units,
                                   pad_to_steps=pad_to_steps or None)
        return self._put_plan(idx, w)

    plan_live_steps = staticmethod(plan_live_steps)

    def epoch_cost(self, plan, use_full: bool = False,
                   n_selected: Optional[int] = None) -> float:
        """Full-epoch-equivalent compute charged for executing ``plan``:
        the bucketed step count — padding rows run a full step before
        being gated — so reported savings include the granule slack
        honestly (DESIGN.md §3)."""
        return plan[0].shape[0] / self.steps_per_epoch_max

    def run_epoch(self, params, opt_state, lr,
                  plan: Tuple[jax.Array, jax.Array]):
        """One scanned epoch.  Returns ``(params, opt_state, losses)``
        with ``losses`` of shape ``(n_steps,)`` — padding steps report 0
        and must be masked out of aggregates with ``plan_live_steps``.
        The passed params/opt_state buffers are donated (see class
        docstring); in pod mode the engine-held ``compress_state`` is
        donated and replaced alongside them."""
        args = self._epoch_args(params, opt_state, lr, plan)
        if self._pod is None:
            params, opt_state, losses, skipped, nsk = self._run(*args)
        else:
            (params, opt_state, self.compress_state, losses, skipped,
             nsk) = self._run(*args)
        self.last_skipped, self.last_n_skipped = skipped, nsk
        return params, opt_state, losses

    def _epoch_args(self, params, opt_state, lr, plan):
        batch_idx, batch_w = plan
        head = (params, opt_state)
        if self._pod is not None:
            head += (self._ensure_compress_state(params),)
        return head + (batch_idx, batch_w, jnp.asarray(lr, jnp.float32),
                       self.units)

    def lower_epoch(self, params, opt_state, lr, plan):
        """``jax.stages.Lowered`` of the one-epoch dispatch that
        ``run_epoch`` would make for these arguments (nothing is
        donated or run) — for compiled-artifact checks."""
        return self._run.lower(*self._epoch_args(params, opt_state, lr,
                                                 plan))

    def run_epochs(self, params, opt_state, lr, prev_loss,
                   plans: Sequence[Tuple[jax.Array, jax.Array]]):
        """A chunk of epochs as ONE dispatch (outer scan over per-epoch
        plans; inner scan over steps; validation + newbob on device).

        ``plans`` must share one shape (all full plans do; subset plans
        within one selection period land in one bucket).  Returns
        ``(params, opt_state, losses (E, n_steps), val_losses (E,),
        lrs (E,), lr_out, prev_loss_out)`` — ``lrs[i]`` is the
        post-update lr after epoch ``i`` (what the host
        ``NewbobState.update`` would have produced), ``val_losses`` is
        NaN-filled when the engine has no ``val_units``.  Metrics cross
        the host boundary once per chunk, when the caller fetches them.
        Inputs are donated like ``run_epoch``."""
        shapes = {tuple(p[0].shape) for p in plans}
        if len(shapes) != 1:
            raise ValueError(f"chunked plans must share one shape, got "
                             f"{sorted(shapes)}")
        # plans arrive already device_put (full_plan/subset_plan, often on
        # the prefetch thread) with their batch axis data-sharded; the
        # stack preserves placement, so no second transfer is needed
        batch_idx = jnp.stack([p[0] for p in plans])
        batch_w = jnp.stack([p[1] for p in plans])
        if self._pod is None:
            (params, opt_state, losses, skipped, nsk, vls, lrs, lr_out,
             prev_out) = self._run_chunk(params, opt_state, self.val_units,
                                         batch_idx, batch_w,
                                         jnp.asarray(lr, jnp.float32),
                                         jnp.asarray(prev_loss, jnp.float32),
                                         self.units)
        else:
            err = self._ensure_compress_state(params)
            (params, opt_state, self.compress_state, losses, skipped, nsk,
             vls, lrs, lr_out, prev_out) = self._run_chunk(
                params, opt_state, err, self.val_units, batch_idx, batch_w,
                jnp.asarray(lr, jnp.float32),
                jnp.asarray(prev_loss, jnp.float32), self.units)
        self.last_skipped, self.last_n_skipped = skipped, nsk
        return params, opt_state, losses, vls, lrs, lr_out, prev_out

    def validate(self, params) -> float:
        """Mean per-unit validation loss as one vmapped call (NaN when the
        engine was built without ``val_units``)."""
        if self.val_units is None:
            return float("nan")
        return float(self._validate(params, self.val_units))


class HostEngine:
    """The legacy per-batch host loop behind the same engine interface —
    the parity oracle (`tests/test_train_engine.py`): one jit call per
    host-assembled batch, one eval call per validation unit.  Plans are
    the unpadded ``(seed, epoch)``-keyed schedules, so batch order is
    byte-identical to the scanned engine's by construction (DESIGN.md
    §1).  With a mesh, only the *selection* units are sharded (the SGD
    step itself stays single-device — sharded training is the scan
    engine's job; pod-axis gradient compression is likewise scan-only)."""

    kind = "host"
    uses_error_feedback = False
    compress_state = None

    def __init__(self, bundle, cfg: TrainConfig,
                 units: Dict[str, Any],
                 val_units: Optional[Dict[str, Any]] = None,
                 batch_units: int = 1,
                 mesh=None, data_axis: str = "data",
                 spec_mode: str = "tp"):
        if cfg.compress_mode != "none":
            raise ValueError(
                f"compress_mode={cfg.compress_mode!r} is scan-engine-only "
                f"(the host loop trains dense on one device); use "
                f"engine='scan' with a data x {cfg.pod_axis} mesh")
        bundle, self.loss_vocab_chunk = autotune_loss_vocab_chunk(
            bundle, units, batch_units)
        self.bundle = bundle
        self.cfg = cfg
        self.batch_units = int(batch_units)
        self.mesh = mesh
        self.units_host = {k: np.asarray(v) for k, v in units.items()}
        place = _data_sharded_put(mesh, data_axis)
        self.units = {k: place(v) for k, v in self.units_host.items()}
        self.val_units = (None if val_units is None else
                          {k: place(np.asarray(v))
                           for k, v in val_units.items()})
        self.n_units = int(self.units_host[next(iter(units))].shape[0])
        self.unit_size = int(self.units_host[next(iter(units))].shape[1])
        self.steps_per_epoch_max = self.n_units // self.batch_units
        self.guard = bool(getattr(cfg, "nonfinite_guard", False))
        self.plan_salt = 0
        self.last_skipped = None
        self.last_n_skipped = None
        self._step = jax.jit(make_step_core(bundle, cfg))
        self._eval = jax.jit(
            lambda params, batch: bundle.per_example_loss(params,
                                                          batch).mean())

    # -- unified interface ---------------------------------------------
    def _plan_seed(self) -> int:
        return self.cfg.seed + 1_000_003 * self.plan_salt

    def full_plan(self, epoch: int):
        idx = epoch_plan(self.n_units, self._plan_seed(), epoch,
                         self.batch_units)
        return idx, np.ones(idx.shape, np.float32)

    def subset_plan(self, indices, weights, epoch: int):
        """Unpadded — the host loop executes exactly the live steps."""
        return subset_epoch_plan(np.asarray(indices), np.asarray(weights),
                                 self._plan_seed(), epoch, self.batch_units)

    plan_live_steps = staticmethod(plan_live_steps)

    def epoch_cost(self, plan, use_full: bool = False,
                   n_selected: Optional[int] = None) -> float:
        """Paper-style charge: the fraction of units trained on (the
        host loop executes exactly the live steps; the dropped
        remainder of a subset is still charged, matching the paper's
        `b_k / n` accounting)."""
        if use_full or n_selected is None:
            return 1.0
        return float(n_selected) / self.n_units

    def shard_state(self, params, opt_state):
        return params, opt_state

    def restore_sharding(self, path: str, arr):
        return None

    def run_epoch(self, params, opt_state, lr, plan):
        """Per-batch host loop over the plan rows — assembles every batch
        in numpy (the same view `full_iterator`/`subset_iterator` yield)
        and dispatches one jit call per step."""
        losses = []
        skipped = []
        for sel, w in zip(*plan):
            batch = {k: v[sel].reshape((-1,) + v.shape[2:])
                     for k, v in self.units_host.items()}
            if "weights" in batch:
                batch = dict(batch, weights=batch["weights"]
                             * np.repeat(w, self.unit_size))
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = self._step(params, opt_state,
                                                    batch, lr)
            losses.append(float(metrics["loss"]))        # repro: noqa[host-sync-loop] -- the host engine IS the per-step parity oracle (DESIGN §1); one sync per step is its definition
            if self.guard:
                skipped.append(float(metrics["skipped"]))  # repro: noqa[host-sync-loop] -- same deliberate per-step oracle sync as the loss fetch above
        if self.guard:
            self.last_skipped = np.asarray(skipped, np.float32)
            self.last_n_skipped = int(sum(skipped))
        return params, opt_state, np.asarray(losses, np.float64)

    def validate(self, params) -> float:
        if self.val_units is None:
            return float("nan")
        n_val = int(jax.tree.leaves(self.val_units)[0].shape[0])
        return float(np.mean([
            float(self._eval(params,
                             {k: v[i] for k, v in self.val_units.items()}))
            for i in range(n_val)]))


def _data_sharded_put(mesh, data_axis: str):
    """Leading-axis ``data`` placement for unit trees (replicated when
    the dim doesn't divide; plain device arrays without a mesh).  Pod
    engines deliberately keep units here too — pod-replicated — and
    split compute on the gathered batch instead (see
    ``EpochEngine._place_units``)."""
    if mesh is None:
        return jnp.asarray
    size = mesh.shape[data_axis]

    def put(v):
        ax = data_axis if v.shape[0] % size == 0 else None
        return jax.device_put(v, NamedSharding(
            mesh, P(ax, *([None] * (np.ndim(v) - 1)))))

    return put


def make_engine(name: str, bundle, cfg: TrainConfig, units,
                val_units=None, batch_units: int = 1, mesh=None,
                data_axis: str = "data", spec_mode: str = "tp"):
    """The one engine factory ``train/loop.py`` consumes: ``"host"`` |
    ``"scan"`` (mesh-native when ``mesh`` is given)."""
    if name == "scan":
        return EpochEngine(bundle, cfg, units, val_units=val_units,
                           batch_units=batch_units, mesh=mesh,
                           data_axis=data_axis, spec_mode=spec_mode)
    if name == "host":
        return HostEngine(bundle, cfg, units, val_units=val_units,
                          batch_units=batch_units, mesh=mesh,
                          data_axis=data_axis, spec_mode=spec_mode)
    raise ValueError(f"unknown engine {name!r}")
