"""Pallas TPU kernel: tiled Gram matrix  K = G G^T  with fp32 accumulation.

This is the one O(n^2 D) operation in Gram-space OMP (core/gm.py); inputs
are bf16/fp32 unit-gradient sketches (n, D).  Tiling: square (tn, tn)
output tiles, sequential accumulation over D tiles in VMEM scratch;
MXU-aligned defaults tn=256, td=512, and any other request is rounded to
a lane-aligned tile (``core/chunking.py:tpu_tile``).

The grid carries a leading partition axis so stage B's per-partition
Grams (``core/pgm.py:partitioned_gm`` needs (P, per, per) from
(P, per, D)) come out of one kernel call: ``omp_gram_batched`` runs the
same body on a ``(P, i, j, k)`` grid with per-partition (1, tn, td)
blocks; ``omp_gram`` is its P = 1 special case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.chunking import LANE, tpu_tile


def _gram_kernel(gi_ref, gj_ref, out_ref, acc_ref):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = gi_ref[0].astype(jnp.float32)
    b = gj_ref[0].astype(jnp.float32)
    acc_ref[...] += a @ b.T

    @pl.when(k == pl.num_programs(3) - 1)
    def _():
        out_ref[...] = acc_ref[...][None]


@functools.partial(jax.jit, static_argnames=("tn", "td", "interpret"))
def omp_gram_batched(g, *, tn: int = 256, td: int = 512,
                     interpret: bool) -> jax.Array:
    """g: (P, n, D) -> (P, n, n) fp32 per-partition Gram matrices."""
    P, n, D = g.shape
    # tn is the output block's minor dim as well as the inputs' second
    # minor one, so it is lane-aligned unless it spans all rows
    tn = tpu_tile(tn, n, LANE)
    td = tpu_tile(td, D, LANE)
    n_pad = (-n) % tn
    d_pad = (-D) % td
    gp = jnp.pad(g, ((0, 0), (0, n_pad), (0, d_pad)))
    Np, Dp = gp.shape[1:]
    grid = (P, Np // tn, Np // tn, Dp // td)

    out = pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tn, td), lambda p, i, j, k: (p, i, k)),
            pl.BlockSpec((1, tn, td), lambda p, i, j, k: (p, j, k)),
        ],
        out_specs=pl.BlockSpec((1, tn, tn), lambda p, i, j, k: (p, i, j)),
        out_shape=jax.ShapeDtypeStruct((P, Np, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tn, tn), jnp.float32)],
        interpret=interpret,
    )(gp, gp)
    return out[:, :n, :n]


@functools.partial(jax.jit, static_argnames=("tn", "td", "interpret"))
def omp_gram(g, *, tn: int = 256, td: int = 512,
             interpret: bool) -> jax.Array:
    """g: (n, D) -> (n, n) fp32 Gram matrix (the P = 1 batched case)."""
    return omp_gram_batched(g[None], tn=tn, td=td, interpret=interpret)[0]
