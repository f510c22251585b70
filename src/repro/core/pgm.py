"""PGM — Partitioned Gradient Matching (paper Algorithm 1).

Every ``R`` epochs:
  stage A  compute per-unit last-layer gradient representations for all
           candidate units (sketched by default; exact = paper-faithful);
  stage B  split units into D partitions; per partition, run gradient
           matching (Algorithm 2 / gm.py) against either the partition's
           own mean gradient (Val=False) or the validation gradient
           (Val=True, robust mode), each with budget b_k/D;
  stage C  concatenate the partial subsets and their weights.

Distribution (docs/DESIGN.md §5): stage A is a plain GSPMD jit (units
sharded over the ``data`` mesh axis, model params over ``model``); stage
B is embarrassingly parallel across partitions and is dispatched with
``shard_map`` over ``data`` in ``pgm_select_sharded`` — the jax-native
equivalent of the paper's "one GM per GPU".

Residency (docs/DESIGN.md §1): ``ResidentSelector`` runs stage A as one
jitted batch-scanned pass over the epoch engine's device-resident unit
buffers — the very same buffers the engine trains from, including their
``data``-axis sharding when the engine was built on a mesh — with the
sketch projections closed over the jit so both the executable and the
projection constants are reused across selection rounds: no per-round
host round-trip, and no second copy of the corpus.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import gm
from repro.core.lastlayer import units_gradients, units_gradients_batched
from repro.core.sketch import Projections
from repro.kernels.backend import resolve_kernel_impl
from repro.kernels.omp_gram.ops import omp_gram_batched_op


class Selection(NamedTuple):
    indices: jax.Array     # (b_k,) global unit ids, -1 padded
    weights: jax.Array     # (b_k,) fp32
    n_selected: jax.Array  # scalar
    errors: jax.Array      # (D,) per-partition final E_lambda


# ---------------------------------------------------------------------------
# Stage B: partitioned OMP over precomputed gradient representations
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_partitions", "budget_per_part",
                                   "nonneg", "val_matching", "kernel_impl",
                                   "solver"))
def partitioned_gm(
    g_units: jax.Array,            # (n, D) unit-gradient vectors
    n_partitions: int,
    budget_per_part: int,
    lam: float = 0.5,
    eps: float = 1e-10,
    nonneg: bool = True,
    val_matching: bool = False,
    g_val: Optional[jax.Array] = None,   # (D,) required when val_matching
    kernel_impl: Optional[str] = None,   # PGMConfig.kernel_impl string
    solver: str = "chol",
) -> Selection:
    n, D_sk = g_units.shape
    P = n_partitions
    assert n % P == 0, f"n units {n} must divide into {P} partitions"
    per = n // P
    gp = g_units.reshape(P, per, D_sk).astype(jnp.float32)

    if val_matching:
        target = jnp.broadcast_to(g_val.astype(jnp.float32), (P, D_sk))
    else:
        # match the partition's own summed gradient: note sum (not mean) so
        # that sum_i w_i g_i can reach it with O(1) weights per unit
        target = gp.sum(axis=1)

    # all P Grams from one batched kernel call (Pallas on TPU / per
    # kernel_impl); c and ||t||^2 are cheap rank-1 contractions
    K = omp_gram_batched_op(gp, impl=kernel_impl)
    c = jnp.einsum("pnd,pd->pn", gp, target)
    tsq = jnp.einsum("pd,pd->p", target, target)

    def one_partition(K_p, c_p, tsq_p):
        return gm.gram_omp(K_p, c_p, tsq_p, budget_per_part, lam, eps,
                           nonneg, solver)

    res = jax.vmap(one_partition)(K, c, tsq)
    offsets = (jnp.arange(P, dtype=jnp.int32) * per)[:, None]
    glob = jnp.where(res.indices >= 0, res.indices + offsets, -1)
    return Selection(
        indices=glob.reshape(-1),
        weights=res.weights.reshape(-1),
        n_selected=res.n_selected.sum(),
        errors=res.error,
    )


# ---------------------------------------------------------------------------
# Full Algorithm 1 selection round (stages A + B)
# ---------------------------------------------------------------------------

def _stage_b(g_units, pgm_cfg, g_val=None, mesh=None,
             data_axis: str = "data") -> Selection:
    """Dispatch stage B (partitioned OMP) over precomputed stage-A
    gradient representations — shard_map over ``data_axis`` when a mesh
    divides the partitions, single-device jit otherwise."""
    n_units = g_units.shape[0]
    budget_total = max(int(pgm_cfg.subset_fraction * n_units), 1)
    D = min(pgm_cfg.n_partitions, n_units)
    budget_per = max(budget_total // D, 1)
    if mesh is not None and _mesh_divides(mesh, data_axis, D, n_units):
        # same code path on 1 and N devices: partitions are distributed
        # over the data axis, each shard runs its OMPs locally
        cfg = pgm_cfg if pgm_cfg.n_partitions == D else \
            dataclasses.replace(pgm_cfg, n_partitions=D)
        return pgm_select_sharded(mesh, data_axis, g_units, cfg, g_val=g_val)
    return partitioned_gm(
        g_units, D, budget_per, pgm_cfg.lam, pgm_cfg.eps,
        pgm_cfg.nonneg_weights, pgm_cfg.val_matching, g_val,
        kernel_impl=_impl_of(pgm_cfg))


def _val_target(gv, n_units: int, pgm_cfg) -> jax.Array:
    """Validation target: mean gradient scaled to the partition mass so
    budgets/weights stay comparable with train matching."""
    D = min(pgm_cfg.n_partitions, n_units)
    return gv.mean(axis=0) * (n_units / D)


def pgm_select(
    bundle,
    params,
    units,                        # batch pytree with leading (n_units,) axis
    pgm_cfg,
    proj: Optional[Projections] = None,
    val_units=None,               # validation units when val_matching
    mesh=None,                    # stage B via shard_map when provided
    data_axis: str = "data",
) -> Selection:
    n_units = jax.tree.leaves(units)[0].shape[0]
    exact = not pgm_cfg.use_sketch
    rt = _router_term_for(bundle, pgm_cfg)
    impl = _impl_of(pgm_cfg)

    g = units_gradients(bundle, params, units, proj, exact=exact,
                        router_term=rt, kernel_impl=impl)
    g_val = None
    if pgm_cfg.val_matching:
        gv = units_gradients(bundle, params, val_units, proj, exact=exact,
                             router_term=rt, kernel_impl=impl)
        g_val = _val_target(gv, n_units, pgm_cfg)
    return _stage_b(g, pgm_cfg, g_val=g_val, mesh=mesh, data_axis=data_axis)


def _router_term_for(bundle, pgm_cfg) -> bool:
    """The MoE router-aware term applies only to sparse-expert bundles
    (DESIGN.md §8); other families silently ignore the flag."""
    return bool(getattr(pgm_cfg, "moe_router_term", False)
                and bundle.cfg.family == "moe")


def _impl_of(pgm_cfg) -> str:
    """Kernel backend string from config, tolerant of older configs that
    predate the ``kernel_impl`` field."""
    return getattr(pgm_cfg, "kernel_impl", "auto") or "auto"


def _soft_random_selection(key, n_units: int, pgm_cfg) -> Selection:
    """Degraded selection when every scorer backend failed: a uniform
    random subset at the configured budget with unit weights — the same
    Selection convention as ``baselines.random_subset`` (inlined here
    because baselines imports this module).  Training proceeds on a
    defensible subset instead of dying mid-run (DESIGN.md §10);
    ``ResidentSelector.degraded_rounds`` counts how often."""
    budget = max(int(pgm_cfg.subset_fraction * n_units), 1)
    idx = jax.random.permutation(key, n_units)[:budget].astype(jnp.int32)
    return Selection(idx, jnp.ones((budget,), jnp.float32),
                     jnp.asarray(budget), jnp.zeros((1,)))


class ResidentSelector:
    """Selection rounds over the epoch engine's device-resident units.

    ``pgm_select`` recomputes stage A from scratch with a sequential
    per-unit map dispatched from host; on the scanned engine the very
    same unit buffers already sit on device, so a resident round is one
    jitted batch-scanned stage-A pass (``units_gradients_batched`` —
    sharded over the ``data`` mesh axis when the units were placed with
    one) followed by the usual stage B.  The sketch ``Projections`` are
    closed over the jit at construction: across rounds both the compiled
    executable and the projection constants are reused instead of being
    re-materialized per call.  With a mesh, stage B additionally routes
    through ``pgm_select_sharded`` exactly like ``pgm_select``.

    Failure policy (DESIGN.md §10): by default (``on_failure="raise"``)
    a failing round raises, so a kernel the backend refuses stops the
    run instead of being replaced unseen.  ``on_failure="soft_random"``
    opts into the degradation ladder: a round that raises on the
    resolved Pallas backend falls back *once* (warn-once) to the
    bit-identical XLA path — both stage A (re-jitted) and stage B read
    the updated ``kernel_impl`` — and if the scorer still fails the
    round degrades to a soft-random subset rather than killing a
    multi-epoch run.

    Usage (see ``train/loop.py``)::

        selector = ResidentSelector(bundle, pgm_cfg, proj, mesh=mesh)
        sel = selector(params, engine.units, val_units=engine.val_units)
    """

    def __init__(self, bundle, pgm_cfg, proj: Optional[Projections] = None,
                 *, chunk_units: Optional[int] = None, mesh=None,
                 data_axis: str = "data", vocab_chunk: int = 8192,
                 on_failure: str = "raise", shard=None, log_fn=None):
        if on_failure not in ("raise", "soft_random"):
            raise ValueError(f"on_failure must be 'raise' or 'soft_random', "
                             f"got {on_failure!r}")
        self.bundle = bundle
        self.cfg = pgm_cfg
        self.mesh = mesh
        self.data_axis = data_axis
        self.on_failure = on_failure
        self._log = log_fn or (lambda s: None)
        self._proj = proj
        self._chunk_units = chunk_units
        self._vocab_chunk = vocab_chunk
        self._exact = not pgm_cfg.use_sketch
        self._rt = _router_term_for(bundle, pgm_cfg)
        # the engine's activation sharder: RNN-T stage A runs the fused
        # loss, whose Pallas lattice needs the mesh's batch partition
        self._shard = shard if bundle.cfg.family == "rnnt" else None
        impl = _impl_of(pgm_cfg)
        # resolve once at build time and surface the decision: "auto" is
        # data-dependent (TPU vs host), and a silent wrong backend is
        # exactly the kind of perf bug a log line catches
        self.kernel_impl = resolve_kernel_impl(impl)
        self._fell_back = False
        self.degraded_rounds = 0
        self._round = 0
        if log_fn is not None:
            log_fn(f"selection kernels: requested={impl} "
                   f"resolved={self.kernel_impl}")
        self._build_stage_a(impl)

    def _build_stage_a(self, impl):
        bundle, proj = self.bundle, self._proj
        chunk_units, vocab_chunk = self._chunk_units, self._vocab_chunk
        exact, rt, shard = self._exact, self._rt, self._shard

        def stage_a(params, units):
            return units_gradients_batched(
                bundle, params, units, proj, chunk_units=chunk_units,
                shard=shard, vocab_chunk=vocab_chunk, exact=exact,
                router_term=rt, kernel_impl=impl)

        # one jit for train and val units alike: the cache keys on unit
        # shapes, so each distinct corpus compiles once and every later
        # round is a cache hit (a kernel fallback rebuilds the jit, so
        # the replacement backend traces fresh)
        self._stage_a = jax.jit(stage_a)

    def stage_a(self, params, units) -> jax.Array:
        """(n_units, D) stage-A gradient representations, jit-cached."""
        return self._stage_a(params, units)

    def _select_round(self, params, units, val_units) -> Selection:
        g = self._stage_a(params, units)
        g_val = None
        if self.cfg.val_matching:
            gv = self._stage_a(params, val_units)
            g_val = _val_target(gv, g.shape[0], self.cfg)
        return _stage_b(g, self.cfg, g_val=g_val, mesh=self.mesh,
                        data_axis=self.data_axis)

    def __call__(self, params, units, val_units=None) -> Selection:
        self._round += 1
        if self.on_failure == "raise":
            return self._select_round(params, units, val_units)
        try:
            return self._select_round(params, units, val_units)
        except Exception as err:
            if self.kernel_impl == "pallas" and not self._fell_back:
                self._fell_back = True
                self._log(f"warning: Pallas selection round failed "
                          f"({err}); falling back to the bit-identical "
                          f"XLA path for all remaining rounds")
                self.kernel_impl = "xla"
                self.cfg = dataclasses.replace(self.cfg,
                                               kernel_impl="xla")
                self._build_stage_a("xla")
                try:
                    return self._select_round(params, units, val_units)
                except Exception as err2:
                    err = err2
            self.degraded_rounds += 1
            n_units = jax.tree.leaves(units)[0].shape[0]
            self._log(f"warning: selection scorer failed ({err}); "
                      f"degrading this round to a soft-random subset")
            return _soft_random_selection(jax.random.PRNGKey(self._round),
                                          n_units, self.cfg)


def _mesh_divides(mesh, axis: str, n_partitions: int, n_units: int) -> bool:
    """shard_map stage B needs whole partitions (and whole units) per
    shard; fall back to the single-device jit when they don't divide."""
    if axis not in mesh.axis_names:
        return False
    size = mesh.shape[axis]
    return n_partitions % size == 0 and n_units % size == 0


# ---------------------------------------------------------------------------
# shard_map distribution of stage B (partitions over the data axis)
# ---------------------------------------------------------------------------

def pgm_select_sharded(mesh, axis: str, g_units, pgm_cfg, g_val=None):
    """Stage B under shard_map: each ``axis`` shard owns n_partitions/|axis|
    whole partitions and runs its OMPs locally with zero cross-device
    traffic; outputs are concatenated by the final all_gather.

    g_units: (n, D) global array (sharded on axis 0 by the caller).
    """
    from jax.sharding import PartitionSpec as P

    n = g_units.shape[0]
    size = mesh.shape[axis]
    D = pgm_cfg.n_partitions
    assert D % size == 0, (D, size)
    budget_total = max(int(pgm_cfg.subset_fraction * n), 1)
    budget_per = max(budget_total // D, 1)
    local_parts = D // size

    def local_fn(g_local, g_val_local):
        # g_local: (n/size, D_sk) -> local partitions
        sel = partitioned_gm(
            g_local, local_parts, budget_per, pgm_cfg.lam, pgm_cfg.eps,
            pgm_cfg.nonneg_weights, pgm_cfg.val_matching,
            g_val_local[0] if pgm_cfg.val_matching else None,
            kernel_impl=_impl_of(pgm_cfg))
        # globalize indices by shard offset
        idx = jax.lax.axis_index(axis) * (n // size)
        indices = jnp.where(sel.indices >= 0, sel.indices + idx, -1)
        return (jax.lax.all_gather(indices, axis, tiled=True),
                jax.lax.all_gather(sel.weights, axis, tiled=True),
                jax.lax.psum(sel.n_selected, axis),
                jax.lax.all_gather(sel.errors, axis, tiled=True))

    gv = (jnp.zeros((1, g_units.shape[1]), jnp.float32) if g_val is None
          else g_val[None])
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(), P(), P(), P()),
        # the OMP while_loop creates fresh (unvarying) carries inside the
        # mapped body; disable varying-manual-axes checking
        check_vma=False,
    )
    indices, weights, n_sel, errors = fn(g_units, gv)
    return Selection(indices, weights, n_sel, errors)


# ---------------------------------------------------------------------------
# Applying a selection: expand selected units into a weighted sub-dataset
# ---------------------------------------------------------------------------

def gather_selected(units, selection: Selection):
    """Materialize the selected units (drop -1 padding is the caller's
    concern; padded entries carry weight 0)."""
    idx = jnp.where(selection.indices >= 0, selection.indices, 0)
    sub = jax.tree.map(lambda a: a[idx], units)
    if "weights" in sub:
        w = selection.weights * (selection.indices >= 0)
        # unit weight broadcasts over the unit's examples
        sub = dict(sub, weights=sub["weights"] * w[:, None])
    return sub
