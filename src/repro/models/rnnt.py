"""RNN-Transducer models: the paper's CRDNN (SpeechBrain Librispeech
transducer recipe; Graves 2012, Ravanelli et al. 2021) and the Conformer
transducer (Gulati et al. 2020), selected by ``RNNTConfig.encoder``.

Transcription network: ``encoder="crdnn"`` is 2 CNN blocks (3x3, stride
2x2) -> 4 bi-LSTM layers -> 2 DNN layers, blind to padding;
``encoder="conformer"`` is ``models/conformer.py``: a length-aware
convolutional subsampling and a stack of Conformer blocks whose batch
norms keep running statistics as non-trained state (the params tree's
``STATE_KEY`` subtree, ``models/common.py``).  Prediction network
(``predictor``): embedding + 1-layer GRU or LSTM.  Joint network:
Linear(enc) + Linear(pred) -> tanh -> Linear to vocab (the layer whose
gradient PGM matches).

The functions below run in evaluation mode (running statistics);
``joint_factors_train`` is the training forward, which normalizes with
the batch's statistics and reports the running statistics after it.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import conformer
from repro.models.common import (STATE_KEY, dense_init, embed_init,
                                 split)


# ---------------------------------------------------------------------------
# Recurrent cells (lax.scan)
# ---------------------------------------------------------------------------

def init_lstm(key, d_in, d_h):
    ks = split(key, 2)
    return {"wx": dense_init(ks[0], d_in, 4 * d_h),
            "wh": dense_init(ks[1], d_h, 4 * d_h),
            "b": jnp.zeros((4 * d_h,))}


def lstm_scan(p, x, reverse=False, state=None, return_state=False):
    """x: (B,T,d_in) -> (B,T,d_h); from ``state`` = (h, c) (zeros when
    None), and with the last (h, c) when ``return_state``."""
    B, T, _ = x.shape
    d_h = p["wh"].shape[0]
    xw = x @ p["wx"].astype(x.dtype) + p["b"].astype(x.dtype)

    def step(carry, xt):
        h, c = carry
        gates = xt + h @ p["wh"].astype(xt.dtype)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    if state is None:
        h0 = jnp.zeros((B, d_h), x.dtype)
        state = (h0, h0)
    last, hs = jax.lax.scan(step, state, jnp.moveaxis(xw, 1, 0),
                            reverse=reverse)
    hs = jnp.moveaxis(hs, 0, 1)
    return (hs, last) if return_state else hs


def lstm_step(p, x_t, h, c):
    """Single LSTM step for greedy transducer decoding: x_t (B,d_in) ->
    (y, (h, c))."""
    y, last = lstm_scan(p, x_t[:, None], state=(h, c), return_state=True)
    return y[:, 0], last


def init_gru(key, d_in, d_h):
    ks = split(key, 2)
    return {"wx": dense_init(ks[0], d_in, 3 * d_h),
            "wh": dense_init(ks[1], d_h, 3 * d_h),
            "b": jnp.zeros((3 * d_h,))}


def gru_scan(p, x, h0=None):
    B, T, _ = x.shape
    d_h = p["wh"].shape[0]
    xw = x @ p["wx"].astype(x.dtype) + p["b"].astype(x.dtype)

    def step(h, xt):
        xr, xz, xn = jnp.split(xt, 3, axis=-1)
        hr, hz, hn = jnp.split(h @ p["wh"].astype(xt.dtype), 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h = (1 - z) * n + z * h
        return h, h

    if h0 is None:
        h0 = jnp.zeros((B, d_h), x.dtype)
    h_last, hs = jax.lax.scan(step, h0, jnp.moveaxis(xw, 1, 0))
    return jnp.moveaxis(hs, 0, 1), h_last


def gru_step(p, x_t, h):
    """Single GRU step for greedy transducer decoding. x_t: (B,d_in)."""
    y, h_new = gru_scan(p, x_t[:, None], h0=h)
    return y[:, 0], h_new


#: Transducer blank symbol.  Training reserves id 0 for blank/pad
#: everywhere (``data/synthetic.py`` samples labels from ``[1, V)``;
#: ``core/rnnt_loss.py`` scores the blank arc on column 0), so decoding
#: uses the same convention.
BLANK_ID = 0


# ---------------------------------------------------------------------------
# RNN-T model
# ---------------------------------------------------------------------------

def _init_crdnn(r, ks) -> Dict:
    p: Dict = {}
    c_in = 1
    for i, c in enumerate(r.cnn_channels):
        std = 1.0 / jnp.sqrt(9.0 * c_in)
        p[f"conv{i}"] = {
            "w": jax.random.normal(ks[i], (3, 3, c_in, c)) * std,
            "b": jnp.zeros((c,)),
        }
        c_in = c
    feat = r.cnn_channels[-1] * (r.n_feats // 4)
    d_in = feat
    for i in range(r.lstm_layers):
        p[f"lstm{i}_f"] = init_lstm(ks[4 + 2 * i], d_in, r.lstm_hidden)
        p[f"lstm{i}_b"] = init_lstm(ks[5 + 2 * i], d_in, r.lstm_hidden)
        d_in = 2 * r.lstm_hidden
    p["dnn0"] = {"w": dense_init(ks[12], d_in, r.dnn_dim),
                 "b": jnp.zeros((r.dnn_dim,))}
    p["dnn1"] = {"w": dense_init(ks[13], r.dnn_dim, r.dnn_dim),
                 "b": jnp.zeros((r.dnn_dim,))}
    return p


def init_params(cfg, key) -> Dict:
    r = cfg.rnnt
    ks = split(key, 16)
    if r.encoder == "conformer":
        p = conformer.init_params(r, ks[0])
        p[STATE_KEY] = conformer.init_stats(r)
    else:
        p = _init_crdnn(r, ks)
    p["pred_embed"] = {"w": embed_init(ks[14], r.vocab_size, r.pred_embed)}
    if r.predictor == "lstm":
        p["pred_lstm"] = init_lstm(ks[15], r.pred_embed, r.pred_hidden)
    else:
        p["pred_gru"] = init_gru(ks[15], r.pred_embed, r.pred_hidden)
    kj = split(jax.random.fold_in(key, 7), 3)
    p["joint"] = {
        "w_enc": dense_init(kj[0], r.enc_dim, r.joint_dim),
        "w_pred": dense_init(kj[1], r.pred_hidden, r.joint_dim),
        "w_out": dense_init(kj[2], r.joint_dim, r.vocab_size),
    }
    return p


def encoder_lengths(cfg, feat_lens):
    """Live encoder frames (the transducer lattice's length) of
    utterances of ``feat_lens`` input frames."""
    if cfg.rnnt.encoder == "conformer":
        return conformer.encoder_lengths(feat_lens)
    return jnp.maximum(feat_lens // cfg.rnnt.time_reduction, 1)


def _encode_crdnn(params, r, feats):
    with jax.named_scope("cnn"):
        x = feats[..., None]                              # (B,T,F,1)
        for i in range(len(r.cnn_channels)):
            w, b = params[f"conv{i}"]["w"], params[f"conv{i}"]["b"]
            x = jax.lax.conv_general_dilated(
                x, w.astype(x.dtype), window_strides=(2, 2), padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            x = jax.nn.relu(x + b.astype(x.dtype))
        B, T4, F4, C = x.shape
        x = x.reshape(B, T4, F4 * C)
    with jax.named_scope("encoder_lstm"):
        for i in range(r.lstm_layers):
            f = lstm_scan(params[f"lstm{i}_f"], x)
            bwd = lstm_scan(params[f"lstm{i}_b"], x, reverse=True)
            x = jnp.concatenate([f, bwd], axis=-1)
    with jax.named_scope("dnn"):
        x = jax.nn.relu(x @ params["dnn0"]["w"].astype(x.dtype)
                        + params["dnn0"]["b"].astype(x.dtype))
        x = jax.nn.relu(x @ params["dnn1"]["w"].astype(x.dtype)
                        + params["dnn1"]["b"].astype(x.dtype))
    return x


def encode(params, cfg, feats, feat_lens=None):
    """feats: (B,T,F) -> (B, T', enc_dim), T' = T//4 (CRDNN) or
    ceil(T/4) (Conformer).  ``feat_lens`` (B,) are the live frames; the
    CRDNN ignores them, the Conformer masks the rest (all frames are live
    when None)."""
    r = cfg.rnnt
    if r.encoder != "conformer":
        return _encode_crdnn(params, r, feats)
    if feat_lens is None:
        feat_lens = jnp.full(feats.shape[:1], feats.shape[1], jnp.int32)
    x, _ = conformer.encode(params, r, params[STATE_KEY], feats, feat_lens)
    return x


def predict(params, cfg, tokens):
    """tokens: (B,U) -> (B, U+1, pred_hidden): position u conditions on
    tokens[<u]; position 0 is the blank-start state."""
    lstm = cfg.rnnt.predictor == "lstm"
    with jax.named_scope("pred_lstm" if lstm else "pred_gru"):
        emb = jnp.take(params["pred_embed"]["w"], tokens, axis=0)
        emb = jnp.pad(emb, ((0, 0), (1, 0), (0, 0)))      # start token = 0
        if lstm:
            return lstm_scan(params["pred_lstm"], emb)
        g, _ = gru_scan(params["pred_gru"], emb)
    return g


def _joint_proj(params, enc, pred):
    dt = enc.dtype
    with jax.named_scope("joint_proj"):
        ze = enc @ params["joint"]["w_enc"].astype(dt)    # (B,T,J)
        zp = pred @ params["joint"]["w_pred"].astype(dt)  # (B,U1,J)
    return ze, zp


def joint_factors(params, cfg, feats, tokens, feat_lens=None):
    """Factors of the joint for the fused transducer loss (DESIGN.md §2):
    -> (ze (B,T',J), zp (B,U+1,J)).  ``tanh(ze[:,:,None] + zp[:,None])``
    is ``joint_hidden``; the fused loss (``core/rnnt_loss.py``) forms it
    row-by-row inside its scan instead of materializing (B,T',U+1,J)."""
    enc = encode(params, cfg, feats, feat_lens)
    return _joint_proj(params, enc, predict(params, cfg, tokens))


def joint_factors_train(params, cfg, feats, tokens, feat_lens,
                        remat: bool = True):
    """The training forward's joint factors: -> ``(ze, zp, state)``.  For
    the Conformer, batch norm takes the batch's statistics over live
    frames, each block is rematerialized when ``remat``, and ``state`` is
    the running statistics after this batch; for the CRDNN this is
    ``joint_factors`` and ``state`` is None."""
    r = cfg.rnnt
    if r.encoder != "conformer":
        return joint_factors(params, cfg, feats, tokens) + (None,)
    enc, state = conformer.encode(params, r, params[STATE_KEY], feats,
                                  feat_lens, train=True, remat=remat)
    return _joint_proj(params, enc, predict(params, cfg, tokens)) + (state,)


def pred_step(params, cfg, tokens, h):
    """One prediction-network step for streaming greedy decode.

    ``tokens``: (B,) int32 — the symbol just emitted; any id < 0 means
    the blank-start state (a zero embedding, exactly what ``predict``
    feeds at position 0 via its left pad).  ``h``: (B, pred_state) state
    (``RNNTConfig.pred_state``: the GRU's hidden state, or the LSTM's
    hidden and cell states side by side).  Returns ``(g (B,
    pred_hidden), h_new)`` — feeding the label sequence through this step
    token by token reproduces ``predict``'s rows exactly
    (tests/test_serve_engine.py).
    """
    emb = jnp.take(params["pred_embed"]["w"], jnp.maximum(tokens, 0), axis=0)
    emb = jnp.where((tokens >= 0)[:, None], emb, 0.0)
    if cfg.rnnt.predictor == "lstm":
        d_h = cfg.rnnt.pred_hidden
        y, (hh, c) = lstm_step(params["pred_lstm"], emb, h[:, :d_h],
                               h[:, d_h:])
        return y, jnp.concatenate([hh, c], axis=-1)
    return gru_step(params["pred_gru"], emb, h)


def pred_start(params, cfg, batch_size: int, dtype=jnp.float32):
    """Blank-start prediction state: ``(g0, h0)`` — what ``predict``
    produces at u=0 before any label is consumed."""
    r = cfg.rnnt
    h0 = jnp.zeros((batch_size, r.pred_state), dtype)
    return pred_step(params, cfg, jnp.full((batch_size,), -1, jnp.int32), h0)


def joint_step(params, enc_t, g):
    """Joint network at one (frame, pred-state) point: ``enc_t``
    (B, dnn_dim), ``g`` (B, pred_hidden) -> logits (B, V).  Identical
    math to one (t, u) cell of ``joint_hidden`` + ``joint_logits``."""
    dt = enc_t.dtype
    ze = enc_t @ params["joint"]["w_enc"].astype(dt)
    zp = g @ params["joint"]["w_pred"].astype(dt)
    return jnp.tanh(ze + zp) @ params["joint"]["w_out"].astype(dt)


def joint_hidden(params, enc, pred):
    """(B,T,De),(B,U1,Dp) -> pre-vocab joint activations (B,T,U1,J).
    This is the activation whose outer product with dL/dlogits forms the
    joint-network gradient PGM matches."""
    dt = enc.dtype
    ze = enc @ params["joint"]["w_enc"].astype(dt)        # (B,T,J)
    zp = pred @ params["joint"]["w_pred"].astype(dt)      # (B,U1,J)
    return jnp.tanh(ze[:, :, None, :] + zp[:, None, :, :])


def joint_logits(params, z):
    return z @ params["joint"]["w_out"].astype(z.dtype)


def forward(params, cfg, feats, tokens, feat_lens=None):
    """-> logits (B, T', U+1, V)."""
    enc = encode(params, cfg, feats, feat_lens)
    pred = predict(params, cfg, tokens)
    z = joint_hidden(params, enc, pred)
    return joint_logits(params, z)
