"""Pallas TPU kernel: sliding-window flash attention (forward).

Used by the local layers of mixtral / starcoder2 / gemma3 /
recurrentgemma.  Grid: (batch*heads, q tiles, band tiles); the band for q
tile i covers kv tiles [i - W/TQ, i] (W must be a multiple of the q tile).
Online softmax state (m, l, acc) lives in VMEM scratch across the
sequential band axis.  Out-of-range band tiles are index-clamped for the
load and fully masked in-kernel (``tile_idx >= 0`` guard prevents the
clamped duplicate from double counting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, window, tq, n_band, scale):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)               # (TQ, hd)
    k = k_ref[...].astype(jnp.float32)               # (TQ, hd)  (band tile)
    v = v_ref[...].astype(jnp.float32)

    tile_idx = i - (n_band - 1) + j                  # absolute kv tile id
    q_pos = i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    kv_pos = tile_idx * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
    dpos = q_pos - kv_pos
    mask = (dpos >= 0) & (dpos < window) & (tile_idx >= 0)

    s = jnp.where(mask, (q @ k.T) * scale, NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "tq", "interpret"))
def swa_attn(q, k, v, *, window: int, tq: int = 256, interpret: bool):
    """q,k,v: (B, H, S, hd), causal sliding-window of ``window`` positions
    (q attends to kv in (q-window, q]).  Returns (B, H, S, hd)."""
    B, H, S, hd = q.shape
    tq = min(tq, S)
    assert S % tq == 0, (S, tq)
    assert window % tq == 0 or window <= tq, (window, tq)
    n_band = max(window // tq, 1) + 1
    scale = 1.0 / (hd ** 0.5)

    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)
    grid = (B * H, S // tq, n_band)

    def kv_index(b, i, j):
        return (b, jnp.maximum(i - (n_band - 1) + j, 0), 0)

    out = pl.pallas_call(
        functools.partial(_swa_kernel, window=window, tq=tq, n_band=n_band,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, tq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, tq, hd), kv_index),
            pl.BlockSpec((None, tq, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, tq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq,), jnp.float32),
            pltpu.VMEM((tq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
