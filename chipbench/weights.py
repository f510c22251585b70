"""Weights from the seed, made on the device in one jitted call.

A weight tree is described by a nested dict whose leaves are
``(shape, std)``: normal draws scaled by ``std``, or zeros where ``std``
is 0.  The benchmark makes the weights; the program and the reference
both take them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _flatten(specs, prefix=()):
    for k in sorted(specs):
        v = specs[k]
        if isinstance(v, dict):
            yield from _flatten(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _nest(pairs):
    out = {}
    for path, leaf in pairs:
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = leaf
    return out


@functools.partial(jax.jit, static_argnames=("layout", "dtype"))
def _draw(key, *, layout, dtype):
    keys = jax.random.split(key, len(layout))
    out = []
    for k, (path, shape, std) in zip(keys, layout):
        if std == 0.0:
            out.append((path, jnp.zeros(shape, dtype)))
        else:
            out.append((path, (jax.random.normal(k, shape, jnp.float32)
                               * std).astype(dtype)))
    return _nest(out)


def make(specs: dict, key, dtype=jnp.float32) -> dict:
    """The weight tree of ``specs`` drawn from ``key``."""
    layout = tuple((path, tuple(shape), float(std))
                   for path, (shape, std) in _flatten(specs))
    return _draw(key, layout=layout, dtype=dtype)
