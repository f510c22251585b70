"""The one traffic generator: a seeded corpus made on the device from the
parameters of a traffic file's ``corpus`` object.

The lengths are the same set for every seed; the seed only orders them
and draws the contents.  So every seed gives a run the same amount of
work, and seeds differ only where real corpora differ: which utterance
sits where, and what it says.

``kind: "asr"`` — utterances shaped like a speech corpus: durations from
a beta law scaled to ``[duration_min_s, duration_max_s]``, ``n_feats``
features per frame at ``fps`` frames per second (zero past the end of the
utterance), and a transcript whose length follows the duration at
``tokens_per_s``, with ids in ``[1, vocab_size)`` (0 is blank and pad).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the key of the fixed length set; the run's seed never reaches it
LENGTHS_KEY = 0


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number below 2**64, host side."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def asr_shape(corpus: dict) -> dict:
    """Padded sizes of an ``asr`` corpus: frames ``T`` and labels ``U``."""
    t_max = int(np.ceil(corpus["duration_max_s"] * corpus["fps"]))
    u_max = int(np.round(corpus["duration_max_s"] * corpus["tokens_per_s"]))
    return {"T": t_max, "U": u_max}


def asr_lengths(corpus: dict) -> tuple:
    """The fixed sets of frame and label counts, sorted by duration
    (host numpy, the same for every seed)."""
    a, b = corpus["duration_beta"]
    lo, hi = corpus["duration_min_s"], corpus["duration_max_s"]
    u = jax.random.beta(jax.random.PRNGKey(LENGTHS_KEY), a, b,
                        (corpus["n_utts"],), jnp.float32)
    dur = np.sort(np.asarray(u, np.float64) * hi)
    dur = np.clip(dur, lo, hi)
    frames = np.round(dur * corpus["fps"]).astype(np.int32)
    labels = np.maximum(np.round(dur * corpus["tokens_per_s"]), 1)
    return frames, labels.astype(np.int32)


@functools.partial(jax.jit,
                   static_argnames=("n_feats", "vocab", "T", "U", "unit"))
def _asr_fill(key, frames, labels, *, n_feats, vocab, T, U, unit):
    n = frames.shape[0]
    kp, kf, kt = jax.random.split(key, 3)
    order = jax.random.permutation(kp, n)
    frames, labels = frames[order], labels[order]
    live_t = jnp.arange(T)[None, :] < frames[:, None]
    feats = jax.random.normal(kf, (n, T, n_feats), jnp.float32)
    feats = jnp.where(live_t[..., None], feats, 0.0)
    live_u = jnp.arange(U)[None, :] < labels[:, None]
    tokens = jax.random.randint(kt, (n, U), 1, vocab, jnp.int32)
    tokens = jnp.where(live_u, tokens, 0)
    units = {"feats": feats, "feat_lens": frames, "tokens": tokens,
             "token_lens": labels, "weights": jnp.ones((n,), jnp.float32)}
    return {k: v.reshape((n // unit, unit) + v.shape[1:])
            for k, v in units.items()}, order


def asr_units(corpus: dict, n_feats: int, vocab: int, seed: int,
              unit_size: int):
    """-> (selection units on the device, duration rank of each
    utterance).  Units are the layout the program's engine and selector
    take, ``(n_units, unit_size, ...)``: ``feats`` fp32 ``(.., T,
    n_feats)``, ``feat_lens``, ``tokens (.., U)``, ``token_lens`` and unit
    ``weights`` of 1."""
    frames, labels = asr_lengths(corpus)
    if len(frames) % unit_size:
        raise ValueError(f"{len(frames)} utterances do not fill units of "
                         f"{unit_size}")
    shp = asr_shape(corpus)
    units, order = _asr_fill(seed_key(seed), jnp.asarray(frames),
                             jnp.asarray(labels), n_feats=n_feats,
                             vocab=vocab, T=shp["T"], U=shp["U"],
                             unit=unit_size)
    return units, np.asarray(order)


def subset_by_rank(order: np.ndarray, fraction_units: int,
                   unit_size: int, seed: int):
    """A subset of ``fraction_units`` units whose duration ranks are a
    fixed, evenly spread set (so every seed trains on the same lengths),
    with unit weights drawn from the seed around 1, as a selection's.

    ``order[i]`` is the duration rank of utterance ``i``.  Returns host
    arrays ``(unit ids, unit weights)``."""
    n_units = len(order) // unit_size
    rank_of_unit = order[: n_units * unit_size].reshape(
        n_units, unit_size).min(axis=1)
    by_rank = np.argsort(rank_of_unit)
    pick = np.floor((np.arange(fraction_units) + 0.5)
                    * n_units / fraction_units).astype(np.int64)
    ids = np.sort(by_rank[pick]).astype(np.int32)
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.5, 1.5, size=fraction_units).astype(np.float32)
    return ids, w
