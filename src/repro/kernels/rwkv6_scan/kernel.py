"""Pallas TPU kernel: chunk-parallel RWKV6 WKV recurrence.

Per (batch*head) lane, chunks are processed sequentially over the minor
grid axis with the (N, N) state carried in VMEM scratch; within a chunk
the pairwise-decay attention matrix is dense MXU work:

  cum_i  = sum_{j<=i} log w_j                  (per channel)
  y      = (r * e^{cum_prev}) @ S
         + [(r_i . k_j e^{cum_{i-1}-cum_j})]_{j<i} @ V  + diag bonus
  S'     = diag(e^{cum_C}) S + (k e^{cum_C - cum})^T V

All exponents are <= 0 (see models/rwkv6.py docstring) — no overflow.
Chunk C=64 and head dim N=64 keep every operand MXU-shaped; VMEM per step
~ (C*C*N + C*N*4 + N*N) * 4B ~= 1.2 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, sout_ref, s_ref,
                *, chunk):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[...].astype(jnp.float32)              # (C, N)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    lw = lw_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)              # (1, N)

    cum = jnp.cumsum(lw, axis=0)                    # (C, N)
    cum_prev = cum - lw
    S = s_ref[...]

    # inter-chunk contribution
    y = (r * jnp.exp(cum_prev)) @ S                 # (C, N)

    # intra-chunk strict-lower pairwise decays
    dif = cum_prev[:, None, :] - cum[None, :, :]    # (C, C, N), <=0 for j<i
    C = chunk
    li = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tri = (li > lj)[:, :, None]
    e = jnp.where(tri, jnp.exp(jnp.minimum(dif, 0.0)), 0.0)
    A = jnp.einsum("in,jn,ijn->ij", r, k, e)
    y = y + A @ v
    # diagonal bonus
    diag = jnp.sum(r * (u * k), axis=1)             # (C,)
    y = y + diag[:, None] * v
    y_ref[...] = y.astype(y_ref.dtype)

    # state update
    tot = cum[-1:, :]                               # (1, N)
    k_dec = k * jnp.exp(tot - cum)
    s_ref[...] = jnp.exp(tot[0])[:, None] * S + k_dec.T @ v

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        sout_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, w, u, *, chunk: int = 64, interpret: bool):
    """r,k,v,w: (B, S, H, N); u: (H, N).  w = decays in (0,1).
    Returns (y (B,S,H,N) fp32, final state (B,H,N,N))."""
    B, S, H, N = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nC = S // chunk

    def lane(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, N)

    rf, kf, vf = lane(r), lane(k), lane(v)
    lwf = lane(jnp.log(jnp.clip(w.astype(jnp.float32), 1e-8, 1.0)))
    uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, 1, N)

    grid = (B * H, nC)
    y, s_out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, 1, N), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((None, N, N), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, N), jnp.float32),
            jax.ShapeDtypeStruct((B * H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf)
    y = y.reshape(B, H, S, N).transpose(0, 2, 1, 3)
    return y, s_out.reshape(B, H, N, N)
