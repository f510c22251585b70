"""Shared building blocks: norms, RoPE, dense init/apply, dtype policy,
and the Sharder protocol that keeps model code mesh-agnostic."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Sharding callback: model code annotates key activations by logical name;
# the launcher maps names to PartitionSpecs.  Tests pass None (identity).
# ---------------------------------------------------------------------------
class Sharder:
    """Maps logical activation names -> sharding constraints.  Base class is
    the identity (single-device tests).  repro.sharding.specs provides the
    mesh-aware implementation."""

    def __call__(self, x: jax.Array, name: str) -> jax.Array:
        return x

    def kv_repeat(self, n_heads: int, n_kv_heads: int) -> int:
        """How many times attention should repeat KV heads so the grouped
        head axis aligns with tensor parallelism (perf iteration 1,
        EXPERIMENTS.md §Perf).  Identity sharder: never."""
        return 1

    def batch_partition(self, n: int):
        """``(mesh, axes)`` over which a batch dim of size ``n`` is split
        for a Pallas kernel, which GSPMD cannot partition and which must
        therefore run under ``shard_map``; None without a mesh."""
        return None


IDENTITY_SHARDER = Sharder()


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float = 1.0):
    std = scale / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)).astype(dtype)


def split(key, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms (computed in fp32, cast back)
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + gamma) parameterization: zero-init gamma == identity
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(dt)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    dt = x.dtype
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                         # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin = jnp.sin(angles)[..., None, :]                   # (..., S, 1, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2 :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation(name: str) -> Callable[[jax.Array], jax.Array]:
    if name == "gelu":
        return jax.nn.gelu
    if name == "silu":
        return jax.nn.silu
    if name == "sq_relu":
        return lambda x: jnp.square(jax.nn.relu(x))
    if name == "tanh":
        return jnp.tanh
    raise ValueError(name)


def ffn_act(ffn_type: str):
    return {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu,
            "gelu": jax.nn.gelu, "sq_relu": activation("sq_relu")}[ffn_type]


# ---------------------------------------------------------------------------
# dtype policy helpers
# ---------------------------------------------------------------------------

def cast_compute(x, cfg) -> jax.Array:
    return x.astype(jnp.dtype(cfg.compute_dtype))


def tree_size_bytes(tree) -> int:
    return sum(np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(tree))


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
