"""Per-unit (mini-batch) last-layer gradients for PGM.

Paper §3: full per-instance RNN-T gradients are prohibitively large (4 MB
each / 111 GB per corpus), so GRAD-MATCH-style methods use only the last
layer — for RNN-T the *joint network*, for decoder LMs the ``lm_head``.

This module computes, per selection unit:
  * the exact flattened last-layer gradient (paper-faithful path), or
  * its tensor-JL sketch (beyond-paper; see core/sketch.py), streamed over
    vocab chunks so neither the (N_tok, V) error matrix nor the (d, V)
    gradient is ever materialized.  The Pallas ``grad_sketch`` kernel is
    the TPU-fused version of ``streamed_er2``; this file is its oracle.

The per-token error scaling matches the training loss exactly:
per-example mean over tokens, then mean over examples, i.e.
``E[b,s] = (softmax - onehot) * mask[b,s] / (n_tok_b * B)``.

Family coverage beyond dense LMs / RNN-T (DESIGN.md §8):

* **Sparse-expert (MoE)** — the last-layer head gradient is blind to the
  router: two units that stress different experts can sketch identically.
  With ``PGMConfig.moe_router_term`` the unit representation is the head
  gradient **concatenated with the per-unit gradient of the total
  training loss (task + load-balance aux) w.r.t. every router weight**
  (``moe_router_grads``), sketched per router leaf with the same ``r_h``
  d-model projection.  The router term costs one autodiff backward per
  unit (vs the closed-form head path), so it is opt-in; default off is
  the paper-faithful last-layer definition.
* **Recurrent carries (RWKV6 ``wkv_scan``, RG-LRU)** — no new gradient
  term: recurrent state is a per-utterance *activation*, zero-initialized
  inside every training forward (``final_hidden`` never threads state
  across units), so the per-unit head gradient is exactly as well-defined
  as for attention stacks.  The engine test matrix
  (``tests/test_archs_smoke.py``) proves the state paths through the
  epoch scan (scan-of-scan) stay host/scan parity-exact, resume
  bit-exactly, and are untouched by weight-0 padding steps.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sketch import Projections, exact_from_factors, sketch_from_factors


# ---------------------------------------------------------------------------
# LM factor extraction
# ---------------------------------------------------------------------------

def lm_unit_factors(bundle, params, batch, shard=None):
    """-> (h (N,d) fp32, targets (N,), scale (N,) fp32).  N = B*(S-1)."""
    from repro.models.common import IDENTITY_SHARDER
    h, targets, mask, _ = bundle.final_hidden(
        params, batch, shard=shard or IDENTITY_SHARDER, remat=False)
    B = h.shape[0]
    denom = jnp.maximum(mask.sum(axis=-1, keepdims=True), 1.0)
    scale = (mask / (denom * B)).astype(jnp.float32)
    d = h.shape[-1]
    return (h.reshape(-1, d).astype(jnp.float32),
            targets.reshape(-1).astype(jnp.int32),
            scale.reshape(-1))


def streamed_er2(h, w_head, targets, scale, r_v, chunk: int = 8192):
    """Computes ``E @ R2`` without materializing E, streaming vocab chunks.

    h: (N,d) fp32; w_head: (d,V); targets (N,); scale (N,);
    r_v: (V,k2).  Returns (N,k2) fp32.
    E[n] = scale[n] * (softmax(h[n] @ W) - onehot(targets[n])).
    """
    N, d = h.shape
    V = w_head.shape[1]
    k2 = r_v.shape[1]
    # chunks-leading pad/reshape/validity layout shared with the fused
    # RNN-T loss (core/chunking.py) so the mask convention cannot drift
    from repro.core.chunking import (chunk_vocab_axis, resolve_vocab_chunk,
                                     vocab_chunk_mask)
    chunk = resolve_vocab_chunk(V, chunk)
    w = chunk_vocab_axis(w_head.astype(jnp.float32), chunk, axis=1)
    rv = chunk_vocab_axis(r_v.astype(jnp.float32), chunk, axis=0)
    valid = vocab_chunk_mask(V, chunk)

    # single pass: flash-style online softmax accumulation of P @ R2 —
    # the unnormalized accumulator is rescaled as the running max moves
    # (§Perf select-iter-2: halves the logits recompute vs two-pass)
    def step(carry, xs):
        m, s, acc = carry
        wc, rc, vc = xs
        lg = jnp.where(vc, h @ wc, -jnp.inf)                  # (N,chunk)
        m_new = jnp.maximum(m, lg.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(lg - m_new[:, None])
        s = s * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + p @ rc
        return (m_new, s, acc), None

    m0 = jnp.full((N,), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((N,), jnp.float32)
    acc0 = jnp.zeros((N, k2), jnp.float32)
    (m, s, acc), _ = jax.lax.scan(step, (m0, s0, acc0), (w, rv, valid))
    er2 = acc / jnp.maximum(s, 1e-30)[:, None]
    er2 = er2 - r_v.astype(jnp.float32)[targets]
    return er2 * scale[:, None]


def lm_unit_sketch(bundle, params, batch, proj: Projections,
                   vocab_chunk: int = 8192, shard=None,
                   kernel_impl: Optional[str] = None) -> jax.Array:
    h, targets, scale = lm_unit_factors(bundle, params, batch, shard)
    w = bundle.head_weight(params)
    # fused gradient+sketch dispatch: Pallas kernel or the streamed_er2
    # XLA path per ``kernel_impl`` (lazy import — ops.py imports our
    # streamed_er2 as its fallback)
    from repro.kernels.grad_sketch.ops import grad_sketch_op
    return grad_sketch_op(h, w, proj.r_h, proj.r_v, targets, scale,
                          vocab_chunk=vocab_chunk,
                          impl=kernel_impl).reshape(-1)


def lm_unit_exact(bundle, params, batch, shard=None) -> jax.Array:
    """Paper-faithful: full flattened lm_head gradient (small models only)."""
    h, targets, scale = lm_unit_factors(bundle, params, batch, shard)
    w = bundle.head_weight(params)
    logits = h @ w.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    e = p - jax.nn.one_hot(targets, w.shape[1], dtype=jnp.float32)
    e = e * scale[:, None]
    return exact_from_factors(h, e)


# ---------------------------------------------------------------------------
# RNN-T (joint network) gradient extraction.
#
# Fused path (cfg.rnnt.loss_impl == "fused", DESIGN.md §2): the joint
# gradient G = dL/dW_out is exactly the ``dw_out`` the fused loss's
# custom_vjp backward emits — alpha/beta occupancies contracted against
# the streamed joint — so ``jax.grad`` of the fused loss w.r.t. the head
# weight alone yields the (J, V) last-layer gradient without ever
# materializing the (B,T,U+1,V) logits, its gradient, or the (B,T,U+1,J)
# activations.  The sketch is then the two-sided projection
# ``R1^T G R2`` (identical in expectation to the factor-side
# ``(H R1)^T (E R2)``, since both equal the projected G).
#
# Dense path: error via autodiff through the materialized lattice — the
# parity oracle.
# ---------------------------------------------------------------------------

def _rnnt_per_example_nll_scale(batch):
    """The training loss's per-example scaling: mean over examples of
    nll / max(u_len, 1)."""
    B = batch["token_lens"].shape[0]
    return 1.0 / (jnp.maximum(batch["token_lens"].astype(jnp.float32), 1.0)
                  * B)


def rnnt_joint_grad(bundle, params, batch, shard=None) -> jax.Array:
    """(J, V) joint-network gradient of the unit's training loss via the
    fused custom_vjp backward (memory-lean; no joint materialization).
    ``shard`` pins the joint factors like the training loss does
    (``act_bsd``; see models/api.py) — identity when None."""
    from repro.core.rnnt_loss import rnnt_loss_fused
    from repro.models import rnnt as rnnt_mod
    from repro.models.common import IDENTITY_SHARDER
    cfg = bundle.cfg
    r = cfg.rnnt
    shard = shard or IDENTITY_SHARDER
    ze, zp = rnnt_mod.joint_factors(params, cfg, batch["feats"],
                                    batch["tokens"])
    ze = shard(ze, "act_bsd")
    zp = shard(zp, "act_bsd")
    t_lens = jnp.maximum(batch["feat_lens"] // r.time_reduction, 1)
    scale = _rnnt_per_example_nll_scale(batch)

    def loss_of_head(w_out):
        per_ex = rnnt_loss_fused(ze, zp, w_out, batch["tokens"], t_lens,
                                 batch["token_lens"],
                                 vocab_chunk=r.loss_vocab_chunk,
                                 lattice_part=shard.batch_partition(
                                     ze.shape[0]))
        return jnp.sum(per_ex * scale)

    return jax.grad(loss_of_head)(
        bundle.head_weight(params).astype(jnp.float32))


def rnnt_unit_factors(bundle, params, batch, shard=None):
    from repro.models import rnnt as rnnt_mod
    from repro.models.common import IDENTITY_SHARDER
    cfg = bundle.cfg
    r = cfg.rnnt
    z, _, _, _ = bundle.final_hidden(
        params, batch, shard=shard or IDENTITY_SHARDER)        # (B,T,U1,J)
    w_out = bundle.head_weight(params)

    def loss_of_logits(logits):
        from repro.core.rnnt_loss import rnnt_loss_from_logits
        t_lens = jnp.maximum(batch["feat_lens"] // r.time_reduction, 1)
        per_ex = rnnt_loss_from_logits(logits, batch["tokens"], t_lens,
                                       batch["token_lens"])
        per_ex = per_ex / jnp.maximum(batch["token_lens"].astype(jnp.float32),
                                      1.0)
        return per_ex.mean()

    logits = rnnt_mod.joint_logits(params, z)
    e = jax.grad(loss_of_logits)(logits.astype(jnp.float32))   # (B,T,U1,V)
    J = z.shape[-1]
    return (z.reshape(-1, J).astype(jnp.float32),
            e.reshape(-1, e.shape[-1]))


def rnnt_unit_sketch(bundle, params, batch, proj: Projections,
                     shard=None) -> jax.Array:
    if bundle.cfg.rnnt.loss_impl == "fused":
        g = rnnt_joint_grad(bundle, params, batch, shard)
        return (proj.r_h.astype(jnp.float32).T @ g
                @ proj.r_v.astype(jnp.float32)).reshape(-1)
    h, e = rnnt_unit_factors(bundle, params, batch, shard)
    return sketch_from_factors(h, e, proj)


def rnnt_unit_exact(bundle, params, batch, shard=None) -> jax.Array:
    if bundle.cfg.rnnt.loss_impl == "fused":
        return rnnt_joint_grad(bundle, params, batch, shard).reshape(-1)
    h, e = rnnt_unit_factors(bundle, params, batch, shard)
    return exact_from_factors(h, e)


# ---------------------------------------------------------------------------
# Sparse-expert (MoE) router-aware gradients (DESIGN.md §8)
# ---------------------------------------------------------------------------

def moe_router_grads(bundle, params, batch, shard=None):
    """Per-unit gradients of the total training loss (task + router
    load-balance aux) w.r.t. every ``router`` weight leaf.

    Returns a list of fp32 arrays shaped like the router leaves (stacked
    pattern-group routers keep their leading group dim).  One autodiff
    backward through the full stack per unit — deliberately NOT a
    closed-form last-layer trick: the router's gradient flows through
    the top-k combine weights and the aux loss, which is the signal the
    head gradient cannot see.  Opt-in via ``PGMConfig.moe_router_term``.
    """
    from repro.models.common import IDENTITY_SHARDER
    shard = shard or IDENTITY_SHARDER
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    r_ix = [i for i, (p, _) in enumerate(flat)
            if "router" in jax.tree_util.keystr(p)]
    if not r_ix:
        raise ValueError(
            f"{bundle.cfg.name}: moe_router_term set but the params tree "
            f"has no 'router' leaves (family={bundle.cfg.family!r})")
    leaves = [l for _, l in flat]

    def loss_of_routers(router_leaves):
        lv = list(leaves)
        for i, v in zip(r_ix, router_leaves):
            lv[i] = v.astype(leaves[i].dtype)
        total, _ = bundle.loss_fn(
            jax.tree_util.tree_unflatten(tdef, lv), batch, shard=shard)
        return total

    return jax.grad(loss_of_routers)(
        [leaves[i].astype(jnp.float32) for i in r_ix])


def moe_unit_sketch(bundle, params, batch, proj: Projections,
                    vocab_chunk: int = 8192, shard=None,
                    kernel_impl: Optional[str] = None) -> jax.Array:
    """Router-aware MoE unit representation: the lm_head sketch
    concatenated with each router gradient projected through ``r_h`` on
    its d_model dim (router weights are (..., d, E), so the same
    projection matrix serves both terms).  The router term itself stays
    on the XLA autodiff path regardless of ``kernel_impl`` — only the
    head block dispatches to the fused kernel."""
    head = lm_unit_sketch(bundle, params, batch, proj, vocab_chunk, shard,
                          kernel_impl)
    rh = proj.r_h.astype(jnp.float32)
    parts = [jnp.einsum("...de,dk->...ke", g, rh).reshape(-1)
             for g in moe_router_grads(bundle, params, batch, shard)]
    return jnp.concatenate([head] + parts)


def moe_unit_exact(bundle, params, batch, shard=None) -> jax.Array:
    """Exact variant: flattened lm_head gradient + raw router gradients."""
    head = lm_unit_exact(bundle, params, batch, shard)
    parts = [g.reshape(-1)
             for g in moe_router_grads(bundle, params, batch, shard)]
    return jnp.concatenate([head] + parts)


# ---------------------------------------------------------------------------
# Unified entry points
# ---------------------------------------------------------------------------

def unit_gradient(bundle, params, batch, proj: Optional[Projections],
                  exact: bool = False, vocab_chunk: int = 8192,
                  shard=None, router_term: bool = False,
                  kernel_impl: Optional[str] = None) -> jax.Array:
    """One selection unit -> gradient representation vector.

    ``router_term`` (MoE family only) appends the router-logit gradient
    term to the head-gradient representation — see module docstring and
    DESIGN.md §8 for the definition and its cost.  ``kernel_impl``
    (``auto``/``pallas``/``xla``) picks the fused grad-sketch backend for
    the LM/MoE head block; the RNN-T sketch already rides the fused
    loss's ``dw_out`` custom_vjp factors, and the exact path is XLA-only."""
    if bundle.cfg.family == "rnnt":
        return (rnnt_unit_exact(bundle, params, batch, shard) if exact
                else rnnt_unit_sketch(bundle, params, batch, proj, shard))
    if router_term and bundle.cfg.family == "moe":
        return (moe_unit_exact(bundle, params, batch, shard) if exact
                else moe_unit_sketch(bundle, params, batch, proj,
                                     vocab_chunk, shard, kernel_impl))
    return (lm_unit_exact(bundle, params, batch, shard) if exact
            else lm_unit_sketch(bundle, params, batch, proj, vocab_chunk,
                                shard, kernel_impl))


def units_gradients(bundle, params, units, proj: Optional[Projections],
                    exact: bool = False, vocab_chunk: int = 8192,
                    router_term: bool = False,
                    kernel_impl: Optional[str] = None) -> jax.Array:
    """units: batch pytree with leading (n_units, ...) axis.
    Returns (n_units, D) fp32.  Sequential lax.map bounds peak memory to a
    single unit's forward pass (the paper's partition rationale)."""
    fn = lambda u: unit_gradient(bundle, params, u, proj, exact, vocab_chunk,
                                 router_term=router_term,
                                 kernel_impl=kernel_impl)
    return jax.lax.map(fn, units)


def _chunk_size(U: int, chunk_units: Optional[int]) -> int:
    """Largest chunk size <= the requested one that divides U."""
    cu = min(chunk_units or max(U // 16, 1), U)
    while U % cu:
        cu -= 1
    return cu


def units_gradients_scanned(bundle, params, units,
                            proj: Optional[Projections],
                            exact: bool = False,
                            chunk_units: Optional[int] = None,
                            vocab_chunk: int = 8192,
                            shard=None,
                            router_term: bool = False,
                            kernel_impl: Optional[str] = None) -> jax.Array:
    """Family-agnostic batched stage A: scan over unit *chunks*, vmap the
    per-unit gradient representation within a chunk.  Peak memory is
    bounded by ``chunk_units`` forward passes (vs one for the fully
    sequential ``units_gradients``, vs all for a flat vmap); the scan keeps
    it a single executable so a jitted selection round dispatches once.
    Used for RNN-T (autodiff through the transducer lattice resists the
    flattened-example trick below) and for the exact/paper-faithful path.
    ``shard`` is forwarded into the per-unit forward pass for activation
    sharding constraints; note that unlike the flattened LM path this
    still scans the (possibly sharded) unit axis, so under a mesh it does
    not avoid the §Perf select-iter-1 redundancy.
    """
    U = jax.tree.leaves(units)[0].shape[0]
    cu = _chunk_size(U, chunk_units)
    xs = jax.tree.map(
        lambda a: a.reshape((U // cu, cu) + a.shape[1:]), units)
    fn = lambda u: unit_gradient(bundle, params, u, proj, exact, vocab_chunk,
                                 shard, router_term=router_term,
                                 kernel_impl=kernel_impl)

    def chunk_fn(_, cb):
        return None, jax.vmap(fn)(cb)

    _, sks = jax.lax.scan(chunk_fn, None, xs)
    return sks.reshape(U, -1)


def units_gradients_batched(bundle, params, units,
                            proj: Optional[Projections] = None,
                            chunk_units: Optional[int] = None,
                            shard=None, vocab_chunk: int = 8192,
                            exact: bool = False,
                            router_term: bool = False,
                            kernel_impl: Optional[str] = None) -> jax.Array:
    """Batched stage-A gradient representations for resident/distributed
    selection rounds.

    ``units_gradients`` maps sequentially over units — correct and
    memory-bounded on one host, but under GSPMD a scan over a *sharded*
    units axis degenerates to every device computing every unit (16x
    redundant compute; §Perf select-iter-1).  Here LM units are flattened
    to an example axis that stays sharded over the data mesh axes
    (batches of ``chunk_units`` units at a time); per-unit sketches are
    recovered with a segment contraction.  RNN-T and the exact
    (paper-faithful) path route through ``units_gradients_scanned`` —
    same chunked single-executable shape, per-unit math inside a vmap.

    This is the kernel of ``core/pgm.ResidentSelector``: jit it once with
    the projections closed over and every selection round reuses both the
    executable and the device-resident ``proj`` constants.
    """
    # RNN-T, exact, and router-aware MoE route through the scanned path:
    # the flattened-example trick below recovers per-unit sketches with a
    # segment contraction over head factors, which cannot express the
    # per-unit autodiff router term (one backward per unit is required)
    if bundle.cfg.family == "rnnt" or exact or \
            (router_term and bundle.cfg.family == "moe"):
        return units_gradients_scanned(bundle, params, units, proj,
                                       exact=exact, chunk_units=chunk_units,
                                       vocab_chunk=vocab_chunk, shard=shard,
                                       router_term=router_term,
                                       kernel_impl=kernel_impl)
    from repro.kernels.grad_sketch.ops import grad_sketch_units_op
    from repro.models.common import IDENTITY_SHARDER
    shard = shard or IDENTITY_SHARDER
    lead = jax.tree.leaves(units)[0].shape
    U, b = lead[0], lead[1]
    flat = jax.tree.map(lambda a: a.reshape((U * b,) + a.shape[2:]), units)
    cu = _chunk_size(U, chunk_units)
    n_chunks = U // cu
    xs = jax.tree.map(
        lambda a: a.reshape((n_chunks, cu * b) + a.shape[1:]), flat)
    w = bundle.head_weight(params)

    def chunk_fn(_, cb):
        h, targets, mask, _ = bundle.final_hidden(params, cb, shard=shard,
                                                  remat=False)
        d = h.shape[-1]
        S = h.shape[1]
        denom = jnp.maximum(mask.sum(axis=-1, keepdims=True), 1.0)
        scale = (mask / (denom * b)).astype(jnp.float32)
        # fused per-unit gradient + two-sided sketch: one kernel call per
        # chunk (Pallas streams the vocab axis in VMEM; the XLA fallback
        # is the historical streamed_er2 + segment-einsum, bit-identical)
        sk = grad_sketch_units_op(
            h.reshape(cu, b * S, d), w, proj.r_h, proj.r_v,
            targets.reshape(cu, b * S), scale.reshape(cu, b * S),
            vocab_chunk=vocab_chunk, impl=kernel_impl)
        return None, sk.reshape(cu, -1)

    _, sks = jax.lax.scan(chunk_fn, None, xs)
    return sks.reshape(U, -1)


def make_proj_for(bundle, key, k1: int = 64, k2: int = 64) -> Projections:
    from repro.core.sketch import make_projections
    cfg = bundle.cfg
    if cfg.family == "rnnt":
        return make_projections(key, cfg.rnnt.joint_dim, cfg.rnnt.vocab_size,
                                k1, k2)
    return make_projections(key, cfg.d_model, cfg.vocab_size, k1, k2)
