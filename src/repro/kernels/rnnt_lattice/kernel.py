"""Pallas TPU kernel: RNN-T lattice wavefront scan (DESIGN.md §2).

Computes the whole (T, U+1) lattice recurrence
  rows[t] = row_update(logaddexp(rows[t-1] + mult[t], add[t]), emit[t])
in one ``pallas_call``: the TPU grid is sequential over T, a VMEM
scratch carries the previous row across grid steps, and the within-row
first-order log-semiring recurrence
  a[u] = logaddexp(base[u], a[u-1] + emit[u])
is solved with a Hillis–Steele doubling scan — ``ceil(log2(U1))``
vectorized (B, U1) steps instead of U1 sequential ones, the in-kernel
twin of the ``lax.associative_scan`` row update in
``core/rnnt_loss.py`` (its oracle; see ``ref.py``).

Combine rule for the pair (c, b) = (emit prefix, partial row):
  (c1, b1) . (c2, b2) = (c1 + c2, logaddexp(b1 + c2, b2))
with identity (0, NEG) shifted in at the row head.

VMEM budget per step: 4 row tiles of (B, U1) fp32 plus the carry —
kilobytes at any realistic (B, U) — so the kernel is HBM-bandwidth
bound on the three (T, B, U1) streams, with no (B, T, U, V) traffic at
all (the vocab never enters the lattice).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _lattice_kernel(mult_ref, add_ref, emit_ref, out_ref, carry_ref, *,
                    n_u: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        carry_ref[...] = jnp.full_like(carry_ref, NEG)

    base = jnp.logaddexp(carry_ref[...] + mult_ref[0], add_ref[0])
    c = emit_ref[0]                                    # (B, U1)
    b = base
    d = 1
    while d < n_u:                                     # Hillis–Steele
        B = b.shape[0]
        c_shift = jnp.concatenate(
            [jnp.zeros((B, d), b.dtype), c[:, :-d]], axis=1)
        b_shift = jnp.concatenate(
            [jnp.full((B, d), NEG, b.dtype), b[:, :-d]], axis=1)
        b = jnp.logaddexp(b_shift + c, b)
        c = c_shift + c
        d *= 2
    carry_ref[...] = b
    out_ref[0] = b


@functools.partial(jax.jit, static_argnames=("interpret",))
def rnnt_lattice(mult, add, emit, *, interpret: bool):
    """mult, add, emit: (T, B, U1) fp32 -> lattice rows (T, B, U1) fp32.

    ``emit[t, :, 0]`` must be NEG (position 0 has no within-row
    predecessor); ``add[0]`` seeds the first row (the virtual row -1 is
    NEG).
    """
    T, B, U1 = mult.shape
    f32 = jnp.float32
    row_spec = pl.BlockSpec((1, B, U1), lambda t: (t, 0, 0))
    return pl.pallas_call(
        functools.partial(_lattice_kernel, n_u=U1),
        grid=(T,),
        in_specs=[row_spec, row_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((T, B, U1), f32),
        scratch_shapes=[pltpu.VMEM((B, U1), f32)],
        interpret=interpret,
    )(mult.astype(f32), add.astype(f32), emit.astype(f32))
