"""Deterministic, shardable batch pipeline.

Selection *units* are fixed mini-batches (the paper's PerBatch
granularity): `make_units` stacks a corpus into (n_units, unit_size, ...)
arrays once; PGM selects unit indices + weights; `subset_iterator` then
re-shuffles the selected units into SGD batches each epoch (paper §4:
"randomly shuffle elements in the subset, divide into mini-batches of
size B, run weighted mini-batch SGD").

Everything is keyed by (seed, epoch) so a restart resumes the exact
stream (fault tolerance: the checkpoint records epoch + microstep).

Two consumers share the plan arrays produced here (DESIGN.md §1/§3):
the scanned epoch engine (`train/engine.py`) gathers batches from them
on device — with ``pad_to_steps`` padding subset plans to a fixed shape
so changing ``n_selected`` between selection rounds never retraces the
epoch executable — and the host iterators below are thin unpadded views
over the same plans, so both execution paths see byte-identical batch
order by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, NamedTuple, Optional, Tuple

import numpy as np

from repro.data.synthetic import ASRCorpus, LMCorpus


def lm_units(corpus: LMCorpus, unit_size: int) -> Dict[str, np.ndarray]:
    """-> dict with leading (n_units, unit_size, ...) arrays."""
    n = (corpus.tokens.shape[0] // unit_size) * unit_size
    toks = corpus.tokens[:n]
    lens = corpus.lengths[:n]
    S = toks.shape[1]
    mask = (np.arange(S)[None, :] < lens[:, None]).astype(np.float32)
    nu = n // unit_size
    return {
        "tokens": toks.reshape(nu, unit_size, S).astype(np.int32),
        "loss_mask": mask.reshape(nu, unit_size, S),
        "weights": np.ones((nu, unit_size), np.float32),
    }


def asr_units(corpus: ASRCorpus, unit_size: int) -> Dict[str, np.ndarray]:
    n = (corpus.feats.shape[0] // unit_size) * unit_size
    nu = n // unit_size
    sh = lambda a: a[:n].reshape((nu, unit_size) + a.shape[1:])
    return {
        "feats": sh(corpus.feats).astype(np.float32),
        "feat_lens": sh(corpus.feat_lens).astype(np.int32),
        "tokens": sh(corpus.tokens).astype(np.int32),
        "token_lens": sh(corpus.token_lens).astype(np.int32),
        "weights": np.ones((nu, unit_size), np.float32),
    }


def unit_durations(units: Dict[str, np.ndarray]) -> np.ndarray:
    """Per-unit total duration (for LargeOnly/LargeSmall baselines)."""
    if "feat_lens" in units:
        return units["feat_lens"].sum(axis=1).astype(np.float32)
    return units["loss_mask"].sum(axis=(1, 2)).astype(np.float32)


def padded_length(units) -> int:
    """Per-example length the units are padded to, in ``unit_durations``'
    measure: frames for ASR units, tokens for LM units."""
    return int((units["feats"] if "feat_lens" in units
                else units["loss_mask"]).shape[2])


class PlanCounts(NamedTuple):
    """What one epoch plan runs, counted on the host.  Positions are
    per-example time steps (``unit_durations``' measure)."""
    steps: int
    live_steps: int
    positions: int          # steps x batch units x unit size x padded length
    live_positions: int     # the real lengths of the live units


def plan_counts(plan_idx: np.ndarray, durations: np.ndarray,
                unit_size: int, padded_len: int) -> PlanCounts:
    """Count a ``(n_steps, batch_units)`` plan of unit ids (padding -1)
    against the units' ``unit_durations``."""
    live = plan_idx >= 0
    steps, batch_units = plan_idx.shape
    return PlanCounts(
        steps, int(live.any(axis=1).sum()),
        steps * batch_units * unit_size * padded_len,
        int(np.asarray(durations, np.float64)[plan_idx[live]].sum()))


# ---------------------------------------------------------------------------
# Epoch plans: the (seed, epoch)-keyed batch schedule as index/weight arrays.
# The scanned epoch engine (train/engine.py) gathers batches from these on
# device; the host iterators below are thin views over the same plans, so
# both execution paths see byte-identical batch order by construction.
# ---------------------------------------------------------------------------

def epoch_plan(n_units: int, seed: int, epoch: int,
               batch_units: int = 1) -> np.ndarray:
    """Full-data epoch schedule -> (n_steps, batch_units) int32 unit ids.

    Seeded shuffle of all units, remainder dropped (warm-start phase).
    The plan is a pure function of ``(seed, epoch)``: a resumed run
    rebuilds byte-identical schedules for the remaining epochs, which is
    what makes checkpoint/resume exact (see ``train/loop.py``).
    """
    order = np.random.default_rng((seed, epoch)).permutation(n_units)
    n_steps = n_units // batch_units
    return order[: n_steps * batch_units].reshape(
        n_steps, batch_units).astype(np.int32)


def subset_epoch_plan(indices, weights, seed: int, epoch: int,
                      batch_units: int = 1,
                      pad_to_steps: Optional[int] = None,
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Weighted-subset epoch schedule -> (unit ids, unit weights), each
    ``(n_steps, batch_units)``.  Drops -1 padding from the selection,
    shuffles the survivors with the (seed, epoch, 1) stream, drops the
    remainder.

    ``pad_to_steps`` (the retrace-free contract used by the scanned epoch
    engine): when given, the plan is padded with *padding rows* up to
    exactly ``(pad_to_steps, batch_units)`` — id ``-1`` and weight ``0`` —
    so every selection round produces the same plan shape regardless of
    ``n_selected`` and one compiled epoch executable serves them all.
    Padding-row semantics downstream (DESIGN.md §3): the engine skips a
    padding row on the device (a ``lax.cond`` on its id), so it runs no
    step, advances neither params nor optimizer state and contributes
    nothing to metrics.  Host iterators never see padding rows (they call
    this with ``pad_to_steps=None``).
    """
    valid = np.asarray(indices) >= 0
    idx = np.asarray(indices)[valid]
    w = np.asarray(weights)[valid]
    order = np.random.default_rng((seed, epoch, 1)).permutation(len(idx))
    idx, w = idx[order], w[order]
    n_steps = len(idx) // batch_units
    shape = (n_steps, batch_units)
    plan_idx = idx[: n_steps * batch_units].reshape(shape).astype(np.int32)
    plan_w = w[: n_steps * batch_units].reshape(shape).astype(np.float32)
    if pad_to_steps is not None:
        if n_steps > pad_to_steps:
            raise ValueError(
                f"subset plan needs {n_steps} steps > pad_to_steps="
                f"{pad_to_steps}")
        n_pad = pad_to_steps - n_steps
        plan_idx = np.concatenate(
            [plan_idx, np.full((n_pad, batch_units), -1, np.int32)])
        plan_w = np.concatenate(
            [plan_w, np.zeros((n_pad, batch_units), np.float32)])
    return plan_idx, plan_w


def full_iterator(units, seed: int, epoch: int,
                  batch_units: int = 1) -> Iterator[Dict[str, np.ndarray]]:
    """Iterate all units in a seeded epoch shuffle (warm-start phase)."""
    nu = units[next(iter(units))].shape[0]
    for sel in epoch_plan(nu, seed, epoch, batch_units):
        yield {k: _merge_units(v[sel]) for k, v in units.items()}


def subset_iterator(units, indices, weights, seed: int, epoch: int,
                    batch_units: int = 1) -> Iterator[Dict[str, np.ndarray]]:
    """Weighted iteration over a PGM/baseline selection."""
    plan_idx, plan_w = subset_epoch_plan(indices, weights, seed, epoch,
                                         batch_units)
    for sel, w in zip(plan_idx, plan_w):
        batch = {k: _merge_units(v[sel]) for k, v in units.items()}
        uw = np.repeat(w, units["weights"].shape[1]).astype(np.float32)
        batch["weights"] = batch["weights"] * uw
        yield batch


def _merge_units(a: np.ndarray) -> np.ndarray:
    """(k, unit, ...) -> (k*unit, ...)."""
    return a.reshape((-1,) + a.shape[2:])


def shard_batch(batch, sharding=None):
    """Host batch -> device arrays (optionally with a NamedSharding)."""
    import jax
    if sharding is None:
        return {k: jax.numpy.asarray(v) for k, v in batch.items()}
    return {k: jax.device_put(v, sharding[k] if isinstance(sharding, dict)
                              else sharding) for k, v in batch.items()}
