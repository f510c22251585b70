"""Conformer transducer encoder (Gulati et al., "Conformer: Convolution-
augmented Transformer for Speech Recognition", Interspeech 2020), length-
aware, with batch norm's running statistics kept as non-trained state.

Shapes: ``d = conformer_dim``, ``H = conformer_heads`` heads of ``dh =
d / H``, ``K = conformer_kernel``, ``C = subsample_channels``, ``F =
n_feats``.  Every norm scale is parametrized as ``1 + g`` with ``g``
initialised to zero.  Dropout and SpecAugment are off.

**Lengths.**  An utterance of ``L`` live input frames has ``ceil(L/4)``
live encoder frames (``encoder_lengths``); every mask below is taken from
them, so an utterance's outputs (and its loss and gradient) do not depend
on how much padding surrounds it.

**Subsampling** (scope ``conformer.subsample``): frames past ``L`` are
zeroed and the time axis is zero-padded to a multiple of 4, so that every
utterance's frames meet the kernels at the same offsets whatever it is
padded to; then two 3x3 stride-2 Conv2d layers of ``C`` channels with
'SAME' padding, each followed by ReLU and by zeroing the frames past its
live length (``F -> F/4`` features, ``T -> ceil(ceil(T/2)/2)`` frames),
and ``Linear(C * F/4 -> d)``.

**Block** (stacked over ``conformer_blocks``, one ``lax.scan`` step each;
on the training path the subsampling and each block are rematerialized
under ``jax.checkpoint``, so that a batch of 32 LibriSpeech-long
utterances fits on one 16 GB chip)::

    x~ = x  + 1/2 FFN(x)                          conformer.ffn
    x' = x~ + MHSA(x~)                            conformer.mhsa
    x" = x' + Conv(x')                            conformer.conv
    y  = LN(x" + 1/2 FFN(x"))                     conformer.ffn

**FFN:** pre-LN, ``Linear(d -> 4d)``, Swish, ``Linear(4d -> d)``.

**MHSA** (Transformer-XL relative positions, Dai et al. 2019): pre-LN;
per head ``score(i, j) = [(q_i + u) . k_j + (q_i + v) . (W_R R_{i-j})] /
sqrt(dh)``, where ``R`` is the sinusoidal encoding (``[sin | cos]``
halves) of ``i - j`` in ``[-(T'-1), T'-1]``, ``W_R`` has no bias and
``u``, ``v`` are learned per head.  Padded keys are masked before the
softmax.  The position term is formed against all ``2T' - 1`` relative
positions and moved into place with the pad-and-reshape shift.

**Conv module:** pre-LN, pointwise ``d -> 2d``, GLU, padded frames zeroed,
depthwise conv of kernel ``K`` with 'SAME' padding (``(K-1)//2`` before,
the rest after), batch norm, Swish, pointwise ``d -> d``.

**Batch norm:** in training the statistics are the batch's mean and
(biased) variance over live frames only, and the step's new running
statistics are ``m * running + (1 - m) * batch`` (``m = BN_MOMENTUM``);
out of training the running statistics normalize.  ``eps = BN_EPS``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.common import dense_init, layer_norm, split

#: LayerNorm epsilon of every Conformer norm
LN_EPS = 1e-5
#: batch norm's running-statistics momentum and epsilon (TensorFlow's)
BN_MOMENTUM = 0.99
BN_EPS = 1e-3
#: score of a masked (padded) key before the softmax
MASKED = -1e9


def encoder_lengths(feat_lens):
    """Live encoder frames of utterances of ``feat_lens`` input frames:
    ``ceil(ceil(L/2)/2) = ceil(L/4)``, at least 1."""
    return jnp.maximum((feat_lens + 3) // 4, 1)


def _live(lens, t: int):
    return jnp.arange(t)[None, :] < lens[:, None]            # (B, t)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def _dense(key, d_in, d_out):
    return dense_init(key, d_in, d_out), jnp.zeros((d_out,))


def _norm(d):
    return jnp.zeros((d,)), jnp.zeros((d,))


def _init_block(key, r) -> Dict:
    d, f, h = r.conformer_dim, r.conformer_ffn_dim, r.conformer_heads
    ks = split(key, 10)

    def ffn(k1, k2):
        w1, b1 = _dense(k1, d, f)
        w2, b2 = _dense(k2, f, d)
        g, b = _norm(d)
        return {"ln_g": g, "ln_b": b, "w1": w1, "b1": b1, "w2": w2, "b2": b2}

    mh = {}
    for name, k in zip("qkvo", ks[2:6]):
        mh[f"w{name}"], mh[f"b{name}"] = _dense(k, d, d)
    mh["ln_g"], mh["ln_b"] = _norm(d)
    mh["w_pos"] = dense_init(ks[6], d, d)
    mh["pos_u"] = jnp.zeros((h, d // h))
    mh["pos_v"] = jnp.zeros((h, d // h))
    pw1_w, pw1_b = _dense(ks[7], d, 2 * d)
    pw2_w, pw2_b = _dense(ks[8], d, d)
    ln_g, ln_b = _norm(d)
    bn_g, bn_b = _norm(d)
    conv = {"ln_g": ln_g, "ln_b": ln_b, "pw1_w": pw1_w, "pw1_b": pw1_b,
            "dw_w": jax.random.normal(ks[9], (r.conformer_kernel, d))
            / np.sqrt(r.conformer_kernel),
            "dw_b": jnp.zeros((d,)), "bn_g": bn_g, "bn_b": bn_b,
            "pw2_w": pw2_w, "pw2_b": pw2_b}
    g, b = _norm(d)
    return {"ffn1": ffn(ks[0], ks[1]), "mhsa": mh, "conv": conv,
            "ffn2": ffn(*split(jax.random.fold_in(key, 1), 2)),
            "ln_g": g, "ln_b": b}


def init_params(r, key) -> Dict:
    """The encoder's trained parameters: ``subsample`` and ``blocks``
    (each leaf stacked over ``conformer_blocks``)."""
    ks = split(key, 4)
    c, f4 = r.subsample_channels, r.n_feats // 4
    sub = {"conv0": {"w": jax.random.normal(ks[0], (3, 3, 1, c)) / 3.0,
                     "b": jnp.zeros((c,))},
           "conv1": {"w": jax.random.normal(ks[1], (3, 3, c, c))
                     / np.sqrt(9.0 * c), "b": jnp.zeros((c,))}}
    sub["proj"] = dict(zip(("w", "b"), _dense(ks[2], c * f4,
                                              r.conformer_dim)))
    blocks = [_init_block(k, r) for k in split(ks[3], r.conformer_blocks)]
    return {"subsample": sub,
            "blocks": jax.tree.map(lambda *a: jnp.stack(a), *blocks)}


def init_stats(r) -> Dict:
    """Batch norm's running statistics before any step: mean 0, var 1,
    one row per block."""
    shape = (r.conformer_blocks, r.conformer_dim)
    return {"mean": jnp.zeros(shape), "var": jnp.ones(shape)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _ln(x, g, b):
    return layer_norm(x, 1.0 + g, b, LN_EPS)


def _subsample(p, feats, feat_lens):
    B, T, _ = feats.shape
    x = jnp.where(_live(feat_lens, T)[..., None], feats, 0.0)
    x = jnp.pad(x, ((0, 0), (0, -T % 4), (0, 0)))[..., None]  # (B,T4,F,1)
    lens = feat_lens
    for i in range(2):
        w, b = p[f"conv{i}"]["w"], p[f"conv{i}"]["b"]
        x = jax.lax.conv_general_dilated(
            x, w.astype(x.dtype), window_strides=(2, 2), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        lens = (lens + 1) // 2
        x = jnp.where(_live(lens, x.shape[1])[..., None, None],
                      jax.nn.relu(x + b.astype(x.dtype)), 0.0)
    B, T4, F4, C = x.shape
    x = x.reshape(B, T4, F4 * C)
    return x @ p["proj"]["w"].astype(x.dtype) + p["proj"]["b"].astype(x.dtype)


def _ffn(p, x):
    h = _ln(x, p["ln_g"], p["ln_b"])
    h = jax.nn.silu(h @ p["w1"].astype(x.dtype) + p["b1"].astype(x.dtype))
    return h @ p["w2"].astype(x.dtype) + p["b2"].astype(x.dtype)


def rel_positions(t: int, d: int, dtype=jnp.float32):
    """Sinusoidal encodings of the relative positions ``t-1, t-2, ...,
    -(t-1)``, ``[sin | cos]`` halves: ``(2t - 1, d)``."""
    pos = jnp.arange(t - 1, -t, -1, dtype=jnp.float32)
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


def _rel_shift(x):
    """``(..., t, 2t-1)`` scores against relative positions ``t-1 ...
    -(t-1)`` -> ``(..., t, t)`` with ``out[i, j]`` at position ``i - j``."""
    *lead, t, _ = x.shape
    pad = [(0, 0)] * len(lead) + [(0, 0), (1, 0)]
    x = jnp.pad(x, pad).reshape(*lead, 2 * t, t)[..., 1:, :]
    return x.reshape(*lead, t, 2 * t - 1)[..., :t]


def _mhsa(p, x, live, n_heads):
    B, T, d = x.shape
    dh = d // n_heads
    dt = x.dtype
    h = _ln(x, p["ln_g"], p["ln_b"])

    def proj(name):
        return (h @ p[f"w{name}"].astype(dt) + p[f"b{name}"].astype(dt)
                ).reshape(B, T, n_heads, dh)

    q, k, v = proj("q"), proj("k"), proj("v")
    pos = (rel_positions(T, d, dt) @ p["w_pos"].astype(dt)).reshape(
        2 * T - 1, n_heads, dh)
    ac = jnp.einsum("bihd,bjhd->bhij", q + p["pos_u"].astype(dt), k)
    bd = jnp.einsum("bihd,khd->bhik", q + p["pos_v"].astype(dt), pos)
    s = (ac + _rel_shift(bd)) / np.sqrt(dh)
    s = jnp.where(live[:, None, None, :], s, MASKED)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
    o = jnp.einsum("bhij,bjhd->bihd", a, v).reshape(B, T, d)
    return o @ p["wo"].astype(dt) + p["bo"].astype(dt)


def _batch_norm(p, x, live, mean, var, train, eps):
    """-> (normalized x, the statistics it used): the batch's over live
    frames in training, else ``(mean, var)``."""
    if train:
        m = live[..., None].astype(jnp.float32)
        n = jnp.maximum(jnp.sum(m), 1.0)
        x32 = x.astype(jnp.float32)
        mean = jnp.sum(x32 * m, axis=(0, 1)) / n
        var = jnp.sum(jnp.square(x32 - mean) * m, axis=(0, 1)) / n
    y = (x - mean.astype(x.dtype)) * jax.lax.rsqrt(
        var.astype(x.dtype) + eps)
    y = y * (1.0 + p["bn_g"].astype(x.dtype)) + p["bn_b"].astype(x.dtype)
    return y, (mean, var)


def _conv_module(p, x, live, mean, var, train, eps):
    dt = x.dtype
    d = x.shape[-1]
    h = _ln(x, p["ln_g"], p["ln_b"])
    h = h @ p["pw1_w"].astype(dt) + p["pw1_b"].astype(dt)
    h = h[..., :d] * jax.nn.sigmoid(h[..., d:])                  # GLU
    h = jnp.where(live[..., None], h, 0.0)
    K = p["dw_w"].shape[0]
    h = jax.lax.conv_general_dilated(
        h, p["dw_w"].astype(dt)[:, None, :], window_strides=(1,),
        padding=[((K - 1) // 2, K - 1 - (K - 1) // 2)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=d)
    h = h + p["dw_b"].astype(dt)
    h, stats = _batch_norm(p, h, live, mean, var, train, eps)
    h = jax.nn.silu(h)
    return h @ p["pw2_w"].astype(dt) + p["pw2_b"].astype(dt), stats


def _block(p, x, live, mean, var, r, train):
    with jax.named_scope("conformer.ffn"):
        x = x + 0.5 * _ffn(p["ffn1"], x)
    with jax.named_scope("conformer.mhsa"):
        x = x + _mhsa(p["mhsa"], x, live, r.conformer_heads)
    with jax.named_scope("conformer.conv"):
        c, stats = _conv_module(p["conv"], x, live, mean, var, train,
                                BN_EPS)
        x = x + c
    with jax.named_scope("conformer.ffn"):
        x = _ln(x + 0.5 * _ffn(p["ffn2"], x), p["ln_g"], p["ln_b"])
    return x, stats


def encode(params, r, stats, feats, feat_lens, *, train: bool = False,
           remat: bool = False) -> Tuple[jax.Array, Optional[Dict]]:
    """``feats`` (B, T, F), ``feat_lens`` (B,) -> ``(enc (B, ceil(T/4),
    d), new running statistics)``.  With ``train`` batch norm takes the
    batch's statistics and the second item is the running statistics
    after this batch; otherwise ``stats`` normalize and it is None.
    ``remat`` rematerializes the subsampling and each block in the
    backward pass."""
    subsample = jax.checkpoint(_subsample) if remat else _subsample
    with jax.named_scope("conformer.subsample"):
        x = subsample(params["subsample"], feats, feat_lens)
    live = _live(encoder_lengths(feat_lens), x.shape[1])

    def body(x, xs):
        p, mean, var = xs
        return _block(p, x, live, mean, var, r, train)

    if remat:
        body = jax.checkpoint(body)
    x, (bmean, bvar) = jax.lax.scan(
        body, x, (params["blocks"], stats["mean"], stats["var"]))
    if not train:
        return x, None
    m = BN_MOMENTUM
    return x, {"mean": m * stats["mean"] + (1.0 - m) * bmean,
               "var": m * stats["var"] + (1.0 - m) * bvar}
