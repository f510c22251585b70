"""What the drivers share: the program's configuration built from a
configuration file, and the leaf norms the training check compares."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registered architecture ``arch`` with every number of the file that
    names a field of the model (or of its ``rnnt`` group) put in place."""
    from repro.configs import get_config
    cfg = get_config(config["arch"])
    top = {f.name for f in dataclasses.fields(cfg)}
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in config.items()
          if k in top and k not in ("name", "rnnt", "moe")}
    cfg = dataclasses.replace(cfg, **kw)
    if cfg.rnnt is not None:
        sub = {f.name for f in dataclasses.fields(cfg.rnnt)}
        rkw = {k: tuple(v) if isinstance(v, list) else v
               for k, v in config.items() if k in sub}
        cfg = dataclasses.replace(cfg, rnnt=dataclasses.replace(
            cfg.rnnt, **rkw))
    return cfg


def check_tree(program_shapes, weights) -> None:
    """Raise unless the benchmark's weights have the program's tree."""
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), weights)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        program_shapes)
    if got != want:
        raise ValueError(f"weight tree differs from the program's: "
                         f"{got} vs {want}")


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return leaf_norms(jax.tree.map(jnp.subtract, a, b))


def norm_gap(got, want, keep=None) -> float:
    """Worst leaf: ``|got - want|`` over the larger of the leaf's
    reference norm and the median leaf's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.median(want)
    if keep is not None:
        got, want = got[keep], want[keep]
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))
