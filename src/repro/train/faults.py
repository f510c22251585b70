"""Deterministic fault injection for the chaos harness (DESIGN.md §10).

Every injector here is *deterministic* — faults fire at a configured
epoch/step, not at random — so each chaos test (tests/test_chaos.py)
asserts an exact documented recovery path:

  * ``FaultPlan.poison_plan``      -> in-scan non-finite guard gates the
                                      step off bit-exactly (engine.py)
  * ``FaultPlan.maybe_fail_prefetch`` -> PlanPrefetcher retries with
                                      capped backoff (plan_prefetch.py)
  * ``FaultPlan.maybe_preempt``    -> PreemptionHandler finishes the
                                      chunk, writes an emergency
                                      checkpoint, exits resumably
  * ``corrupt_checkpoint`` / ``tamper_arrays`` -> restore refuses the
                                      step, ``restore_latest_intact``
                                      falls back to the previous one
  * ``failing_selection_kernels``  -> ResidentSelector falls back
                                      pallas -> xla -> soft-random

Injectors fire *once* per ``FaultPlan`` instance: after a watchdog
rollback the replayed epochs run clean, which is exactly the transient
fault model the recovery semantics are written for.
"""
from __future__ import annotations

import contextlib
import os
import signal
import threading
from typing import Optional, Tuple

import numpy as np


class FaultPlan:
    """A schedule of deterministic, fire-once faults threaded through
    ``train_with_selection(fault_plan=...)``.

    ``nan_step``/``inf_step`` are ``(epoch, step)`` pairs poisoning one
    plan-weight row (the weights multiply into the per-example loss, so
    the poison propagates into loss and gradients on device);
    ``nan_epoch`` poisons every step of one epoch — enough consecutive
    skips to trip the divergence watchdog.  ``drop_step`` turns one plan
    row into padding (ids -1, weight 0) instead — not a fault but the
    *reference* for the guard's documented semantics: a guarded-off
    non-finite step must be bit-identical to the run that trained the
    same schedule with that batch as a padding row, which the scan
    skips.  ``prefetch_fail_epochs``
    raises from inside the plan builder the first time each listed
    epoch's plan is built.  ``preempt_after_epoch`` raises SIGTERM in
    the loop's own thread once that epoch's chunk completes.
    """

    def __init__(self, *, nan_step: Optional[Tuple[int, int]] = None,
                 inf_step: Optional[Tuple[int, int]] = None,
                 nan_epoch: Optional[int] = None,
                 drop_step: Optional[Tuple[int, int]] = None,
                 prefetch_fail_epochs: Tuple[int, ...] = (),
                 preempt_after_epoch: Optional[int] = None):
        self.nan_step = nan_step
        self.inf_step = inf_step
        self.nan_epoch = nan_epoch
        self.drop_step = drop_step
        self.prefetch_fail_epochs = tuple(prefetch_fail_epochs)
        self.preempt_after_epoch = preempt_after_epoch
        self._fired = set()

    def _once(self, tag) -> bool:
        if tag in self._fired:
            return False
        self._fired.add(tag)
        return True

    # -- plan poisoning (caught by the in-scan non-finite guard) --------
    def poison_plan(self, epoch: int, plan):
        idx, w = plan
        w = np.array(w, np.float32, copy=True)
        if (self.nan_step is not None and self.nan_step[0] == epoch
                and self._once(("nan_step", epoch))):
            w[self.nan_step[1] % w.shape[0]] = np.nan
        if (self.inf_step is not None and self.inf_step[0] == epoch
                and self._once(("inf_step", epoch))):
            w[self.inf_step[1] % w.shape[0]] = np.inf
        if self.nan_epoch == epoch and self._once(("nan_epoch", epoch)):
            w[:] = np.nan
        if (self.drop_step is not None and self.drop_step[0] == epoch
                and self._once(("drop_step", epoch))):
            idx = np.array(idx, np.int32, copy=True)
            row = self.drop_step[1] % w.shape[0]
            idx[row] = -1
            w[row] = 0.0
        return idx, w

    # -- prefetch worker crash (caught by PlanPrefetcher retries) -------
    def maybe_fail_prefetch(self, epoch: int):
        if (epoch in self.prefetch_fail_epochs
                and self._once(("prefetch", epoch))):
            raise RuntimeError(f"injected prefetch failure at epoch "
                               f"{epoch}")

    # -- preemption (caught by PreemptionHandler) -----------------------
    def maybe_preempt(self, epoch: int):
        if (self.preempt_after_epoch is not None
                and epoch >= self.preempt_after_epoch
                and self._once("preempt")):
            signal.raise_signal(signal.SIGTERM)


class PreemptionHandler:
    """SIGTERM/SIGINT -> set a flag; the training loop finishes the
    in-flight chunk, writes an emergency checkpoint through the async
    writer and returns with ``History.preempted`` and a resumable
    manifest (DESIGN.md §10).  Installing from a non-main thread is a
    no-op (``signal.signal`` only works on the main thread) — the chunk
    dispatch still runs, preemption handling is simply owned by
    whichever loop lives on the main thread."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT)

    def __init__(self, log_fn=None):
        self._log = log_fn or (lambda s: None)
        self.triggered = False
        self._prev = {}

    def _handle(self, signum, frame):
        self.triggered = True
        self._log(f"received signal {signum}; checkpointing and exiting "
                  f"after the in-flight chunk")

    def install(self) -> "PreemptionHandler":
        if threading.current_thread() is not threading.main_thread():
            return self
        try:
            for s in self.SIGNALS:
                self._prev[s] = signal.signal(s, self._handle)
        except ValueError:      # embedded interpreters without signal API
            self._prev.clear()
        return self

    def uninstall(self):
        for s, prev in self._prev.items():
            try:
                signal.signal(s, prev)
            except ValueError:
                pass
        self._prev.clear()


# ---------------------------------------------------------------------------
# checkpoint corruption
# ---------------------------------------------------------------------------

def corrupt_checkpoint(ckpt_dir: str, step: Optional[int] = None,
                       n_bytes: int = 64) -> str:
    """Flip bytes in the middle of a checkpoint's ``arrays.npz`` — a
    deterministic stand-in for disk/transfer corruption.  The damaged
    archive fails at decode (zip CRC) or at the manifest's per-array
    sha256, and ``restore_latest_intact`` must fall back to the previous
    intact step.  Returns the damaged file's path."""
    from repro.train import checkpoint as ckpt_mod
    step = ckpt_mod.latest_step(ckpt_dir) if step is None else step
    path = os.path.join(ckpt_dir, f"step_{step}", "arrays.npz")
    with open(path, "r+b") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        pos = size // 2
        f.seek(pos)
        chunk = f.read(min(n_bytes, max(size - pos, 1)))
        f.seek(pos)
        f.write(bytes(b ^ 0xFF for b in chunk))
    return path


def tamper_arrays(ckpt_dir: str, step: Optional[int] = None, keys=None):
    """Rewrite ``arrays.npz`` with perturbed values for ``keys`` (default
    all) while leaving the manifest untouched: a *valid* archive whose
    contents no longer match their recorded sha256.  This exercises the
    checksum verification proper — ``corrupt_checkpoint`` byte-flips the
    zip container, which fails earlier at decode — and lets a test
    assert that ``restore`` names *every* corrupted array.  Returns the
    list of tampered keys."""
    from repro.train import checkpoint as ckpt_mod
    step = ckpt_mod.latest_step(ckpt_dir) if step is None else step
    path = os.path.join(ckpt_dir, f"step_{step}", "arrays.npz")
    data = np.load(path)
    arrays = {k: np.array(data[k]) for k in data.files}
    data.close()
    targets = list(keys) if keys is not None else list(arrays)
    for k in targets:
        arrays[k] = arrays[k] + np.ones((), arrays[k].dtype)
    np.savez(path, **arrays)
    return targets


# ---------------------------------------------------------------------------
# selection-kernel failure
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def failing_selection_kernels(impls=("pallas",)):
    """Patch ``repro.core.pgm.units_gradients_batched`` so stage A raises
    for the listed kernel backends.  ``ResidentSelector`` resolves the
    module global at trace time and re-jits on fallback, so a selector
    retrying on the XLA path sees the unpatched function for
    ``kernel_impl="xla"``.  Pass ``("all",)`` (or list every backend) to
    simulate total scorer failure and exercise the soft-random
    degradation."""
    from repro.core import pgm as pgm_mod
    orig = pgm_mod.units_gradients_batched

    def wrapper(*args, **kwargs):
        impl = kwargs.get("kernel_impl")
        if "all" in impls or impl in impls:
            raise RuntimeError(f"injected kernel failure ({impl!r})")
        return orig(*args, **kwargs)

    pgm_mod.units_gradients_batched = wrapper
    try:
        yield
    finally:
        pgm_mod.units_gradients_batched = orig
