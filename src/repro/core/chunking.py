"""Shared vocab pad/reshape/validity chunking layout (DESIGN.md §2).

Two vocab-streamed consumers scan a head matrix chunk by chunk so no
``(..., V)`` tensor is ever fully live: the fused RNN-T loss's joint
head (``core/rnnt_loss.py:_vocab_chunks``) and the LM last-layer sketch
(``core/lastlayer.py:streamed_er2``).  Their zero-padding and
column-validity conventions must be *identical* — a drifted mask turns
padding columns into real logits and silently changes loss values — so
the layout lives here once and both import it.

Layout contract:

* the vocab axis is zero-padded up to ``n_chunks * chunk`` and reshaped
  into ``(n_chunks, chunk)`` with ``n_chunks`` moved to the front
  (``chunk_vocab_axis``), the xs-leading shape a ``lax.scan`` consumes;
* ``vocab_chunk_mask`` marks which columns of each chunk are real vocab
  entries (``False`` on the zero-padding of the last chunk) — consumers
  must mask padded columns *before* any softmax/logsumexp, since a
  zero-padded logit is a real score of 0, not a missing column.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def resolve_vocab_chunk(V: int, chunk: int) -> int:
    """Effective chunk width: ``<= 0`` means one chunk of the whole
    vocab; larger-than-vocab requests are capped at ``V`` (no point
    padding past the vocabulary)."""
    return V if chunk <= 0 else min(int(chunk), V)


# One budget for every vocab-streaming consumer that must keep its live
# (rows, chunk) slab in fast memory: half of a TPU v5e core's 16 MB VMEM,
# leaving the other half for the non-streamed operands and double
# buffering.  The same number is a sane host-cache working-set bound, so
# the CPU reference paths share it rather than special-casing.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024
LANE = 128                       # TPU minor-dim tile (fp32 lane count)
SUBLANE = 8                      # TPU second-minor tile (fp32)


def tpu_tile(want: int, full: int, align: int) -> int:
    """A block size Mosaic accepts along a dim of size ``full``: the
    whole dim when ``want`` covers it, else ``want`` rounded up to
    ``align`` (``LANE`` for a minor dim, ``SUBLANE`` for a second-minor
    one) and capped at the whole dim."""
    if want >= full:
        return full
    return min(-(-int(want) // align) * align, full)


def auto_vocab_chunk(n_rows: int, V: int, *, dtype_bytes: int = 4,
                     budget_bytes: int = VMEM_BUDGET_BYTES,
                     lane: int = LANE) -> int:
    """Auto-tuned vocab chunk width from ``(live rows, V, memory budget)``.

    Returns ``V`` whenever the whole ``(n_rows, V)`` slab fits the budget
    — small/smoke vocabs keep the single-chunk layout (and its exact
    numerics) untouched.  Otherwise the largest lane-aligned chunk whose
    slab fits, floored at one lane.  Shared by the fused RNN-T loss's
    ``loss_vocab_chunk`` auto-tune (``train/engine.py``, rows =
    ``B * (U+1) + joint_dim``) and the ``grad_sketch`` kernel's vocab
    tiling (rows = ``tn + d``).
    """
    n_rows = max(int(n_rows), 1)
    if n_rows * V * dtype_bytes <= budget_bytes:
        return V
    chunk = budget_bytes // (n_rows * dtype_bytes)
    chunk = max((chunk // lane) * lane, lane)
    return min(chunk, V)


def n_vocab_chunks(V: int, chunk: int) -> int:
    return -(-V // chunk)


def vocab_chunk_mask(V: int, chunk: int) -> jax.Array:
    """Column-validity mask ``(n_chunks, chunk)``: True for real vocab
    columns, False for the zero-padding of the last chunk."""
    nc = n_vocab_chunks(V, chunk)
    return jnp.arange(nc * chunk).reshape(nc, chunk) < V


def chunk_vocab_axis(x: jax.Array, chunk: int, axis: int = -1) -> jax.Array:
    """Zero-pad ``x`` along its vocab ``axis`` to a multiple of ``chunk``
    and split that axis into ``(n_chunks, chunk)``, moving ``n_chunks``
    to the front — the chunks-leading layout every vocab-streaming scan
    consumes as its xs.

    ``(d, V)`` with ``axis=1`` -> ``(nc, d, chunk)``;
    ``(V, k)`` with ``axis=0`` -> ``(nc, chunk, k)``.
    """
    axis = axis % x.ndim
    V = x.shape[axis]
    nc = n_vocab_chunks(V, chunk)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, nc * chunk - V)
    xp = jnp.pad(x, pad)
    xp = xp.reshape(x.shape[:axis] + (nc, chunk) + x.shape[axis + 1:])
    return jnp.moveaxis(xp, axis, 0)


def vocab_chunks(x: jax.Array, chunk: int, axis: int = -1,
                 ) -> Tuple[jax.Array, jax.Array]:
    """``(chunked x, validity mask)`` in one call — the common case."""
    return (chunk_vocab_axis(x, chunk, axis),
            vocab_chunk_mask(x.shape[axis % x.ndim], chunk))
