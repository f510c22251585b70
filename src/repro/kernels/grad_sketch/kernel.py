"""Pallas TPU kernel: fused last-layer gradient sketch (DESIGN.md §2/§9).

Computes, per selection unit,  sketch = (H R1)^T @ (E R2)  where
  E = diag(scale) * (softmax(H W) - onehot(targets))
without materializing the (N, V) error/probability matrix, the (N, k2)
``E R2`` intermediate, or the (d, V) gradient.  Vocab is streamed
tile-by-tile from HBM into VMEM with an online-softmax (flash-style)
normalization over the vocab axis — the TPU-native reformulation of the
paper's gradient-memory problem.

The grid is unit-blocked: ``(U, row tiles, vocab tiles)`` with the vocab
axis minor (the TPU grid is sequential over minor axes, so VMEM scratch
carries running state across vocab tiles and the (1, k1, k2) output
block of unit ``u`` accumulates across its row tiles).  ``grad_sketch``
(one sketch over all rows) is the ``U = 1`` special case of
``grad_sketch_units`` — one kernel body serves both the per-unit op and
the resident selector's batched stage A.

Two sequential-grid kernels:
  1. ``_lse_kernel``     — running logsumexp of H W over vocab tiles;
  2. ``_sketch_kernel``  — accumulates  P_tile @ R2_tile  into an er2
     scratch, finalizes  er2 = (er2 - R2[targets]) * scale  at the last
     vocab tile, and accumulates  (H R1)_tile^T @ er2  into the unit's
     output block.

The vocab tile ``tv`` defaults to the shared VMEM-budget resolver
(``core/chunking.py:auto_vocab_chunk`` with ``tn + d`` live rows — the
(tn, tv) logits tile plus the (d, tv) head slab), the same resolver the
engine uses to auto-tune the fused RNN-T loss's ``loss_vocab_chunk``; an
explicit ``tv`` is capped at it.  Tiles are rounded to what Mosaic
accepts (``core/chunking.py:tpu_tile``): ``tn`` to sublanes, ``tv`` to
lanes, unless either spans its whole axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.chunking import LANE, SUBLANE, auto_vocab_chunk, tpu_tile

NEG = -1e30


def _lse_kernel(h_ref, w_ref, logz_ref, m_ref, s_ref, *, v_total, tv):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)

    h = h_ref[0].astype(jnp.float32)                # (TN, d)
    w = w_ref[...].astype(jnp.float32)              # (d, TV)
    logits = h @ w                                  # MXU
    col = j * tv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(col < v_total, logits, NEG)

    m_prev = m_ref[...]                             # (TN, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    s_ref[...] = (s_ref[...] * jnp.exp(m_prev - m_new)
                  + jnp.exp(logits - m_new).sum(axis=1, keepdims=True))
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        logz = m_ref[...] + jnp.log(jnp.maximum(s_ref[...], 1e-30))
        logz_ref[...] = logz[None]


def _sketch_kernel(h_ref, w_ref, rv_ref, logz_ref, rvt_ref, scale_ref,
                   hr_ref, out_ref, er2_ref, *, v_total, tv):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j == 0)
    def _():
        er2_ref[...] = jnp.zeros_like(er2_ref)

    h = h_ref[0].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    logits = h @ w                                  # (TN, TV)
    col = j * tv + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    p = jnp.where(col < v_total, jnp.exp(logits - logz_ref[0]), 0.0)
    er2_ref[...] += p @ rv_ref[...].astype(jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        er2 = (er2_ref[...] - rvt_ref[0].astype(jnp.float32))
        er2 = er2 * scale_ref[0]
        out_ref[...] += (hr_ref[0].astype(jnp.float32).T @ er2)[None]


@functools.partial(jax.jit, static_argnames=("tn", "tv", "interpret"))
def grad_sketch_units(h, w, r_h, r_v, targets, scale, *, tn: int = 256,
                      tv: int = 0, interpret: bool):
    """h (U,n,d); w (d,V); r_h (d,k1); r_v (V,k2); targets (U,n);
    scale (U,n) -> per-unit sketches (U, k1, k2) fp32.

    Padded rows (n not a tile multiple) ride through with scale 0, so
    they contribute nothing to the finalized er2 or the output block.
    """
    U, n, d = h.shape
    V = w.shape[1]
    k1, k2 = r_h.shape[1], r_v.shape[1]
    tn = tpu_tile(tn, n, SUBLANE)
    # the VMEM budget caps an explicit vocab tile too: a (d, tv) head
    # slab past it is refused by the TPU compiler
    auto = auto_vocab_chunk(tn + d, V)
    tv = tpu_tile(auto if tv <= 0 else min(tv, auto), V, LANE)

    n_pad = (-n) % tn
    v_pad = (-V) % tv
    hp = jnp.pad(h, ((0, 0), (0, n_pad), (0, 0)))
    wp = jnp.pad(w, ((0, 0), (0, v_pad)))
    rvp = jnp.pad(r_v, ((0, v_pad), (0, 0)))
    tp = jnp.pad(targets, ((0, 0), (0, n_pad)))
    # per-row scalars ride as (U, rows, 1) columns: a (1, tn) block of a
    # (U, rows) array has a second-minor dim of 1 != U, which Mosaic
    # refuses, and the column broadcasts against (tn, tv) tiles as is
    sp = jnp.pad(scale, ((0, 0), (0, n_pad)))[..., None]
    np_, Vp = n + n_pad, V + v_pad
    gn, gv = np_ // tn, Vp // tv

    # small host-side precomputations (negligible FLOPs; see module doc)
    hr = hp.astype(jnp.float32) @ r_h.astype(jnp.float32)      # (U,np_,k1)
    rvt = r_v.astype(jnp.float32)[jnp.clip(tp, 0, V - 1)]      # (U,np_,k2)

    logz = pl.pallas_call(
        functools.partial(_lse_kernel, v_total=V, tv=tv),
        grid=(U, gn, gv),
        in_specs=[
            pl.BlockSpec((1, tn, d), lambda u, i, j: (u, i, 0)),
            pl.BlockSpec((d, tv), lambda u, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, tn, 1), lambda u, i, j: (u, i, 0)),
        out_shape=jax.ShapeDtypeStruct((U, np_, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tn, 1), jnp.float32),
                        pltpu.VMEM((tn, 1), jnp.float32)],
        interpret=interpret,
    )(hp, wp)

    sketch = pl.pallas_call(
        functools.partial(_sketch_kernel, v_total=V, tv=tv),
        grid=(U, gn, gv),
        in_specs=[
            pl.BlockSpec((1, tn, d), lambda u, i, j: (u, i, 0)),
            pl.BlockSpec((d, tv), lambda u, i, j: (0, j)),
            pl.BlockSpec((tv, k2), lambda u, i, j: (j, 0)),
            pl.BlockSpec((1, tn, 1), lambda u, i, j: (u, i, 0)),
            pl.BlockSpec((1, tn, k2), lambda u, i, j: (u, i, 0)),
            pl.BlockSpec((1, tn, 1), lambda u, i, j: (u, i, 0)),
            pl.BlockSpec((1, tn, k1), lambda u, i, j: (u, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, k1, k2), lambda u, i, j: (u, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((U, k1, k2), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tn, k2), jnp.float32)],
        interpret=interpret,
    )(hp, wp, rvp, logz, rvt, sp, hr)
    return sketch


@functools.partial(jax.jit, static_argnames=("tn", "tv", "interpret"))
def grad_sketch(h, w, r_h, r_v, targets, scale, *, tn: int = 256,
                tv: int = 0, interpret: bool):
    """h (N,d); w (d,V); r_h (d,k1); r_v (V,k2); targets (N,); scale (N,)
    -> sketch (k1, k2) fp32.  The U = 1 case of ``grad_sketch_units``."""
    return grad_sketch_units(h[None], w, r_h, r_v, targets[None],
                             scale[None], tn=tn, tv=tv,
                             interpret=interpret)[0]
