"""Optimizers (from scratch — no optax offline): SGD(+momentum), AdamW,
gradient clipping, and the paper's "newbob" scheduler (anneal lr by a
factor when relative validation improvement drops below a threshold).
Optimizer states are pytrees mirroring the params, so they inherit the
params' sharding (ZeRO-3-style under FSDP specs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


def tree_all_finite(tree) -> jax.Array:
    """Traced bool scalar: every leaf of ``tree`` is free of NaN/Inf.

    Reference checker for the non-finite step guard's semantics
    (DESIGN.md §10).  The jitted step itself doesn't pay for this
    leafwise sweep: gradient clipping already computes the global norm,
    and any NaN/Inf leaf poisons that sum of squares, so the in-scan
    guard checks ``isfinite(gnorm)`` — one scalar — and feeds it into
    ``gate_step`` (a poisoned step advances nothing, bit-exactly, with
    no host sync)."""
    leaves = jax.tree.leaves(tree)
    ok = jnp.bool_(True)
    for l in leaves:
        ok = ok & jnp.all(jnp.isfinite(l))
    return ok


def clip_by_global_norm(grads, max_norm: float):
    n = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), n


# ---------------------------------------------------------------------------
# SGD (+ momentum) — the paper trains with plain SGD at lr 1-2
# ---------------------------------------------------------------------------

def sgd_init(params, momentum: float = 0.0):
    if momentum == 0.0:
        return {"step": jnp.zeros((), jnp.int32)}
    return {"step": jnp.zeros((), jnp.int32),
            "mu": jax.tree.map(jnp.zeros_like, params)}


def sgd_update(params, grads, state, lr, momentum: float = 0.0,
               weight_decay: float = 0.0):
    step = state["step"] + 1
    if weight_decay:
        grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
    if momentum:
        mu = jax.tree.map(lambda m, g: momentum * m + g, state["mu"], grads)
        upd = mu
        new_state = {"step": step, "mu": mu}
    else:
        upd = grads
        new_state = {"step": step}
    params = jax.tree.map(lambda p, u: (p - lr * u).astype(p.dtype),
                          params, upd)
    return params, new_state


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def adamw_init(params):
    z = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"step": jnp.zeros((), jnp.int32),
            "m": jax.tree.map(z, params),
            "v": jax.tree.map(z, params)}


def adamw_update(params, grads, state, lr, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay: float = 0.0):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
                     state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_
                     + (1 - b2) * jnp.square(g.astype(jnp.float32)),
                     state["v"], grads)
    bc1 = 1 - b1 ** t
    bc2 = 1 - b2 ** t

    def upd(p, m_, v_):
        u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)
        if weight_decay:
            u = u + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * u).astype(p.dtype)

    params = jax.tree.map(upd, params, m, v)
    return params, {"step": step, "m": m, "v": v}


def make_optimizer(name: str):
    if name == "sgd":
        return sgd_init, sgd_update
    if name == "adamw":
        return adamw_init, adamw_update
    raise ValueError(name)


def gate_step(step_on, new_tree, old_tree):
    """The step gate (DESIGN.md §3, §10): select ``new_tree`` where
    ``step_on`` (a traced boolean scalar) and ``old_tree`` otherwise,
    leafwise.

    A gated-off step must advance *nothing*: no parameter update, no step
    counter tick, no Adam moment decay.  ``jnp.where`` on a scalar predicate
    lowers to a select, so a gated-off step returns the old buffers
    bit-identically — the same state as a weight-0 padding row, which the
    scanned epoch skips with a ``lax.cond`` before the step (its live
    rows pass a gate that is True; ``engine.make_step_core`` says why).
    """
    return jax.tree.map(lambda a, b: jnp.where(step_on, a, b),
                        new_tree, old_tree)


def make_update_for(cfg):
    """Bind a TrainConfig's optimizer hyper-parameters once, so the host
    loop and the scanned epoch engine share one (init, update) pair:
    ``init(params) -> state``; ``update(params, grads, state, lr[, step_on])``.

    ``step_on`` (optional traced bool scalar) is the step's gate (the
    scanned epoch's row liveness and the non-finite guard): when False
    the update is a bit-exact no-op for both params and optimizer state
    (``gate_step``); when ``None`` (the host loop without the guard) no
    gating ops are emitted at all.  Padding rows never reach the update:
    the scanned epoch skips them.
    """
    init, update = make_optimizer(cfg.optimizer)
    kw = {"momentum": cfg.momentum} if cfg.optimizer == "sgd" else {}

    def init_fn(params):
        return init(params, cfg.momentum) if cfg.optimizer == "sgd" \
            else init(params)

    def update_fn(params, grads, state, lr, step_on=None):
        new_p, new_s = update(params, grads, state, lr,
                              weight_decay=cfg.weight_decay, **kw)
        if step_on is None:
            return new_p, new_s
        return gate_step(step_on, new_p, params), \
            gate_step(step_on, new_s, state)

    return init_fn, update_fn


# ---------------------------------------------------------------------------
# newbob scheduler (paper: lr 2.0, anneal 0.8 on rel. improvement < 0.0025)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class NewbobState:
    lr: float
    prev_loss: float = float("inf")

    def update(self, val_loss: float, anneal_factor: float = 0.8,
               improvement_threshold: float = 0.0025) -> "NewbobState":
        if self.prev_loss != float("inf"):
            rel = (self.prev_loss - val_loss) / max(abs(self.prev_loss), 1e-9)
            if rel < improvement_threshold:
                return NewbobState(self.lr * anneal_factor, val_loss)
        return NewbobState(self.lr, val_loss)
