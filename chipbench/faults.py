"""Faults planted in the program under test, and the control, to show
that a run's check reads them as not correct (``chipbench/tests`` at a
small size, ``chipbench/calibrate.py`` on the chip at the cell's own
size).

Each is a context manager that replaces one function of the program for
as long as it is open; build the cell's driver inside it.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def state_unchanged():
    """A training step that returns its parameters and optimizer state
    unchanged."""
    import repro.train.engine as engine
    real = engine.make_update_for

    def make_update_for(cfg):
        init, _ = real(cfg)
        return init, lambda params, grads, state, lr, step_on=None: (
            params, state)
    return _patched(engine, "make_update_for", make_update_for)


def half_batch():
    """The loss over the first half of each batch only, its weighted mean
    taken over that half."""
    import jax.numpy as jnp

    import repro.models.api as api
    real = api._weighted

    def _weighted(per_ex, batch, aux):
        half = per_ex.shape[0] // 2
        w = batch.get("weights")
        w = jnp.ones_like(per_ex) if w is None else w
        w = w * (jnp.arange(per_ex.shape[0]) < half)
        return real(per_ex, dict(batch, weights=w), aux)
    return _patched(api, "_weighted", _weighted)


def control():
    """Not a fault: the cell's control.  The reference, computed in the
    precision below the configuration's, takes the program's place, so
    that the check compares its readings with the reference's."""
    import chipbench.drivers.train as train
    return _patched(train.Driver, "readings", train.Driver.control_readings)


#: the faults each driver's cells can have
FAULTS = {"train": {"state_unchanged": state_unchanged,
                    "half_batch": half_batch}}
