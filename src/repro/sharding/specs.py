"""Partition specs for params, optimizer state, batches, caches and
activations (DESIGN.md §5).

Strategy: 2-D sharding — FSDP over the data axes (params gathered per
layer by the compiler) + tensor parallelism over ``model``.  All rules are
divisibility-guarded: a dim is sharded only when the mesh axis divides it,
so one policy covers every assigned arch (e.g. seamless' vocab 256206 is
not 16-divisible -> embedding falls back to FSDP-only; starcoder2's 24
heads -> head_dim sharding instead of head sharding).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.common import Sharder


def _axsize(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


def _div(dim: int, mesh, axes) -> bool:
    return axes is not None and dim % _axsize(mesh, axes) == 0


def _best_axes(dim: int, axes_pref):
    """Longest prefix of ``axes_pref`` whose size product divides dim."""
    if axes_pref is None:
        return None
    axes = (axes_pref,) if isinstance(axes_pref, str) else tuple(axes_pref)
    return axes  # divisibility handled by guarded()


class SpecBuilder:
    """mode:
      'tp'         — FSDP over data axes + tensor parallel over 'model'
                     (serving; MoE experts ride the 'model' axis when
                     divisible)
      'expert'     — like 'tp', but MoE expert weights shard their
                     leading n_experts dim over a dedicated ``expert``
                     mesh axis when the mesh has one, else over the FSDP
                     data axes (``P(expert-or-fsdp, ...)``), and router
                     params stay REPLICATED so every shard routes with
                     identical logits under top-k dispatch.  Expert-dim
                     indivisibility is a hard ValueError (naming the
                     arch) instead of a silent fallback — a half-sharded
                     expert bank trains wrong quietly.
      'fsdp_sp'    — batch over data axes, SEQUENCE over 'model', params
                     fully FSDP (dense-attention training: removes the
                     per-layer TP activation all-reduces; perf iter 4)
      'fsdp_batch' — batch over ALL axes, params fully FSDP (recurrent
                     archs whose sequence axis cannot shard)
    """

    def __init__(self, mesh, *, fsdp: bool = True, mode: str = "tp",
                 pod_axis: Optional[str] = None,
                 arch: Optional[str] = None):
        """``pod_axis`` names a slow cross-pod mesh axis that params (and
        their mirrored optimizer/error-feedback states) must NOT shard
        over — the standard multi-pod layout is FSDP *within* a pod and
        plain replication *across* pods, with the cross-pod gradient
        collective handled explicitly (``train/compress.py``,
        DESIGN.md §5).  The pod axis is excluded from both the data-
        parallel and the FSDP axis sets; meshes without a ``model`` axis
        (e.g. ``data x pod``) degrade gracefully to tp=None.  An
        ``expert`` axis is likewise never used for data parallelism —
        it exists solely for the expert-weight dim in ``mode='expert'``.
        ``arch`` names the model config in error messages."""
        self.mesh = mesh
        self.mode = mode
        self.pod_axis = pod_axis
        self.arch = arch
        has_model = "model" in mesh.axis_names
        dp = tuple(a for a in mesh.axis_names
                   if a not in ("model", "expert") and a != pod_axis)
        self.dp_axes = dp
        self.all_axes = tuple(a for a in mesh.axis_names
                              if a != pod_axis and a != "expert")
        self.dp = dp if len(dp) > 1 else (dp[0] if dp else None)
        if mode in ("tp", "expert"):
            self.tp = "model" if has_model else None
            self.fsdp = self.dp if fsdp else None
            #: the expert-or-fsdp axis for MoE expert-weight leading dims
            self.expert = ("expert" if "expert" in mesh.axis_names
                           else self.fsdp)
        elif mode == "fsdp_sp":
            self.tp = None                     # no tensor parallelism
            self.fsdp = self.all_axes          # params over everything
            self.seq = "model" if has_model else None
        elif mode == "fsdp_batch":
            self.tp = None
            self.fsdp = self.all_axes
            self.seq = None
        else:
            raise ValueError(mode)

    # -- parameter rule, dispatched on key-path + shape ---------------------
    def param_spec(self, path: str, shape: Tuple[int, ...]) -> P:
        m = self.mesh
        nd = len(shape)
        is_moe = ".moe." in path or "'moe'" in path

        def guarded(*axes):
            out = []
            for dim, ax in zip(shape, axes):
                out.append(ax if _div(dim, m, ax) else None)
            # never shard one mesh axis twice
            seen = set()
            final = []
            for ax in out:
                key = tuple(ax) if isinstance(ax, tuple) else ax
                if ax is not None and key in seen:
                    final.append(None)
                    continue
                if ax is not None:
                    seen.add(key)
                final.append(ax)
            return P(*final)

        if nd == 0:
            return P()
        if nd == 1:
            return P(None)
        # stacked-group params have 1-2 leading stack dims; identify the
        # trailing "real" dims by known key names
        leaf = re.split(r"[.\[\]']+", path.strip("."))
        name = next((t for t in reversed(leaf) if t and t != "w"), "")
        core = _PARAM_RULES.get(name)
        if is_moe and name in ("w_in", "w_gate"):
            core = ("experts", "fsdp", "tp")        # (E, d, ff)
        if is_moe and name == "w_out":
            core = ("experts", "tp", "fsdp")        # (E, ff, d)
        if is_moe and name == "router" and self.mode == "expert":
            # routers replicate in expert mode: every shard must compute
            # identical top-k routing decisions for the dispatched slots
            # (and the GSPMD mean-psum of their grads over data) to agree
            return P(*([None] * nd))
        if "embed" in path and nd >= 2:
            # vocab over 'model' in every mode: the fwd gather needs only a
            # small (B,S,d) combine, and unembed logits come out
            # vocab-sharded (no full-table replication; §Perf iter 5)
            core = (("tp", "fsdp") if self.mode in ("tp", "expert")
                    else ("model", None))
        if "lm_head" in path and nd >= 2:
            core = (("fsdp", "tp") if self.mode in ("tp", "expert")
                    else (None, "model"))
        if core is None:
            core = ("fsdp", "tp") if nd >= 2 else (None,)
        core_nd = len(core)
        lead = nd - core_nd
        if lead < 0:        # e.g. rule for stacked but leaf unstacked
            core = core[-nd:]
            lead = 0
        axes = [None] * lead + [self._resolve(c, shape[lead + i])
                                for i, c in enumerate(core)]
        return guarded(*axes)

    def _resolve(self, tag, dim):
        if tag is None:
            return None
        if tag == "fsdp":
            return self.fsdp
        if tag == "tp":
            return self.tp
        if tag == "experts":
            if self.mode == "expert":
                ax = self.expert
                if ax is None or not _div(dim, self.mesh, ax):
                    raise ValueError(
                        f"arch {self.arch or '<unknown>'}: MoE expert dim "
                        f"{dim} does not divide over expert axis {ax!r} "
                        f"(size {_axsize(self.mesh, ax)}) in "
                        f"mode='expert' — resize the mesh or drop the "
                        f"expert axis instead of silently half-sharding "
                        f"the expert bank")
                return ax
            return self.tp if _div(dim, self.mesh, self.tp) else None
        return tag

    def param_specs(self, shapes_tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(shapes_tree)
        specs = [self.param_spec(jax.tree_util.keystr(p), l.shape)
                 for p, l in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    # -- batches ------------------------------------------------------------
    def batch_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        B = shape[0]
        if self.mode == "fsdp_batch":
            ax = self.all_axes if _div(B, self.mesh, self.all_axes) else (
                self.dp if _div(B, self.mesh, self.dp) else None)
            return P(ax, *([None] * (len(shape) - 1)))
        dp = self.dp if _div(B, self.mesh, self.dp) else None
        rest = [None] * (len(shape) - 1)
        if (self.mode == "fsdp_sp" and len(shape) >= 2
                and _div(shape[1], self.mesh, "model")):
            rest[0] = "model"                  # sequence over 'model'
        return P(dp, *rest)

    def batch_specs(self, tree):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = [self.batch_spec(jax.tree_util.keystr(p), l.shape)
                 for p, l in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    # -- decode caches --------------------------------------------------------
    def cache_spec(self, path: str, shape: Tuple[int, ...],
                   batch: int) -> P:
        """KV caches: batch over dp when divisible, else the sequence dim
        (long-context, batch=1) over dp; kv-heads over model when
        divisible, else head_dim (flash-decoding-style layouts are a perf
        iteration, see EXPERIMENTS.md §Perf)."""
        nd = len(shape)
        if nd == 0:
            return P()
        leaf = re.split(r"[.\[\]']+", path.strip("."))
        name = next((t for t in reversed(leaf) if t), "")
        # locate the batch dim: caches may carry leading stack dims
        try:
            b_idx = shape.index(batch)
        except ValueError:
            b_idx = None
        axes = [None] * nd
        dp_used = False
        if b_idx is not None and _div(batch, self.mesh, self.dp):
            axes[b_idx] = self.dp
            dp_used = True
        if name in ("k", "v") and nd >= 3:
            # (..., B, L, KV, hd)
            kv_dim, hd_dim = shape[-2], shape[-1]
            if _div(kv_dim, self.mesh, self.tp):
                axes[-2] = self.tp
            elif _div(hd_dim, self.mesh, self.tp):
                axes[-1] = self.tp
            if not dp_used and _div(shape[-3], self.mesh, self.dp):
                axes[-3] = self.dp          # seq-sharded long context
        elif name in ("ck", "cv") and nd >= 3:
            if _div(shape[-2], self.mesh, self.tp):
                axes[-2] = self.tp
            elif _div(shape[-1], self.mesh, self.tp):
                axes[-1] = self.tp
        elif name == "S" and nd >= 3:       # rwkv state (..., B, H, N, N)
            if _div(shape[-3], self.mesh, self.tp):
                axes[-3] = self.tp
        elif name in ("h", "conv") and nd >= 2:   # rg-lru state (..., B, w)
            if _div(shape[-1], self.mesh, self.tp):
                axes[-1] = self.tp
        return P(*axes)

    def cache_specs(self, tree, batch: int):
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = [self.cache_spec(jax.tree_util.keystr(p), l.shape, batch)
                 for p, l in flat]
        return jax.tree_util.tree_unflatten(treedef, specs)

    # -- shardings ------------------------------------------------------------
    def to_shardings(self, spec_tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))


# trailing-dim rules per param name: tags resolve via SpecBuilder._resolve
_PARAM_RULES: Dict[str, Tuple] = {
    "wq": ("fsdp", "tp"),
    "wk": ("fsdp", "tp"),
    "wv": ("fsdp", "tp"),
    "wo": ("tp", "fsdp"),
    "wg": ("fsdp", "tp"),
    "wr": ("fsdp", "tp"),
    "w_in": ("fsdp", "tp"),
    "w_gate": ("fsdp", "tp"),
    "w_gate_branch": ("fsdp", "tp"),
    "w_out": ("tp", "fsdp"),
    "router": ("fsdp", None),
    "w_enc": ("fsdp", "tp"),
    "w_pred": ("fsdp", "tp"),
    "wa": ("fsdp", "tp"),
    "wx": ("fsdp", "tp"),
    "wh": ("fsdp", "tp"),
    "decay_w1": ("fsdp", None),
    "decay_w2": (None, "tp"),
    "ddlerp_w1": ("fsdp", None),
    "ddlerp_w2": (None, None, "fsdp"),
    "conv_w": (None, "tp"),
    "pred_embed": ("tp", "fsdp"),
}


class MeshSharder(Sharder):
    """Activation-constraint callback handed into model forwards."""

    def __init__(self, mesh, *, enable: bool = True, mode: str = "tp",
                 pod_axis: Optional[str] = None,
                 arch: Optional[str] = None):
        self.mesh = mesh
        self.b = SpecBuilder(mesh, mode=mode, pod_axis=pod_axis, arch=arch)
        self.enable = enable

    def kv_repeat(self, n_heads: int, n_kv_heads: int) -> int:
        """Smallest r dividing the GQA group count with (n_kv*r) divisible
        by the TP degree, so attention scores shard over heads instead of
        being computed via per-block all-reduces (head_dim contraction).
        Returns 1 when no such r exists (falls back to head_dim sharding)
        or when KV heads already align."""
        if not self.enable or self.b.mode not in ("tp", "expert") \
                or "model" not in self.mesh.axis_names:
            return 1
        tp = _axsize(self.mesh, "model")
        if n_kv_heads % tp == 0 or tp == 1:
            return 1
        g = n_heads // n_kv_heads
        for r in range(2, g + 1):
            if g % r == 0 and (n_kv_heads * r) % tp == 0:
                return r
        return 1

    def batch_partition(self, n: int):
        if not self.enable:
            return None
        dp = self.b.dp
        return self.mesh, (dp if _div(n, self.mesh, dp) else None)

    def __call__(self, x, name: str):
        if not self.enable:
            return x
        m, dp = self.mesh, self.b.dp
        shape = x.shape
        spec = None
        if self.b.mode not in ("tp", "expert"):
            # fsdp_sp: (B, S, ...) activations -> batch over dp, seq over
            # 'model'; fsdp_batch: batch over all axes
            if x.ndim >= 2 and name in ("act_bsd", "act_ff", "act_q",
                                        "act_kv", "act_q_flat"):
                if self.b.mode == "fsdp_batch":
                    ax = (self.b.all_axes
                          if _div(shape[0], m, self.b.all_axes) else
                          (dp if _div(shape[0], m, dp) else None))
                    spec = P(ax, *([None] * (x.ndim - 1)))
                else:
                    seq_ax = ("model"
                              if _div(shape[1], m, "model") else None)
                    spec = P(dp if _div(shape[0], m, dp) else None, seq_ax,
                             *([None] * (x.ndim - 2)))
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(m, spec))
            return x
        tp = "model" if "model" in m.axis_names else None
        if name == "act_bsd" and x.ndim == 3:
            spec = P(dp if _div(shape[0], m, dp) else None, None, None)
        elif name == "act_ff" and x.ndim == 3:
            spec = P(dp if _div(shape[0], m, dp) else None, None,
                     tp if _div(shape[2], m, tp) else None)
        elif name in ("act_q", "act_kv"):
            # (B,S,KV,G,hd) or (B,S,KV,hd): prefer head sharding, fall back
            # to head_dim
            axes = [dp if _div(shape[0], m, dp) else None] + \
                   [None] * (x.ndim - 1)
            if _div(shape[2], m, tp):
                axes[2] = tp
            elif _div(shape[-1], m, tp):
                axes[-1] = tp
            spec = P(*axes)
        elif name == "act_q_flat" and x.ndim == 3:
            spec = P(dp if _div(shape[0], m, dp) else None, None,
                     tp if _div(shape[2], m, tp) else None)
        elif name == "moe_expert_in" or name == "moe_expert_out":
            # (E, G, C, d): the all-to-all boundary — the E dim rides the
            # expert axis in mode='expert', the TP axis otherwise
            eax = self.b.expert if self.b.mode == "expert" else tp
            axes = [eax if _div(shape[0], m, eax) else None,
                    dp if _div(shape[1], m, dp) else None, None, None]
            spec = P(*axes)
        elif name == "moe_dispatch":
            spec = P(dp if _div(shape[0], m, dp) else None, None, None, None)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))
