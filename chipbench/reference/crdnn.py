"""Plain reference of the CRDNN transducer, written from the model's
description in straightforward ``jax.numpy``: forward, transducer loss,
gradient and AdamW.

It imports nothing of the program under test.  Departures from a textbook
CRDNN that the program's model makes, and that this reference follows so
that the two compute the same function:

- the bi-LSTM runs over every padded frame (no length mask), so the
  backward direction starts in the padding;
- the LSTM's forget gate carries a constant bias of +1 inside the sigmoid,
  gate order (input, forget, cell, output);
- the prediction network's GRU computes ``n = tanh(x_n + r * (h W_n))``;
- the joint is ``tanh(enc W_enc + pred W_pred) W_out`` with no biases;
- the per-utterance loss is the transducer NLL over the number of labels.

Work is done in blocks of utterances so that the dense joint, about
0.3 GB per utterance at LibriSpeech lengths, fits next to nothing else.
``dtype`` is the precision of the network, weights and activations:
float32 at ``highest`` matmul precision for the reference, bfloat16 for
the control, which is the bf16-compute step a later change would take
(ROADMAP S6).  The transducer lattice and the loss stay float32 in both:
a bfloat16 forward variable cannot hold a path score of thousands to the
nearest unit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
#: bytes of dense joint logits a block may hold
BLOCK_BYTES = 1.2e9


def param_specs(cfg: dict) -> dict:
    """Shapes and init scales of every weight, as ``(shape, std)``; a std
    of 0 means zeros.  The tree is the program's parameter tree."""
    def dense(d_in, d_out):
        return ((d_in, d_out), 1.0 / np.sqrt(d_in))

    p, c_in = {}, 1
    for i, c in enumerate(cfg["cnn_channels"]):
        p[f"conv{i}"] = {"w": ((3, 3, c_in, c), 1.0 / np.sqrt(9.0 * c_in)),
                         "b": ((c,), 0.0)}
        c_in = c
    d_in, h = cfg["cnn_channels"][-1] * (cfg["n_feats"] // 4), \
        cfg["lstm_hidden"]
    for i in range(cfg["lstm_layers"]):
        for side in ("f", "b"):
            p[f"lstm{i}_{side}"] = {"wx": dense(d_in, 4 * h),
                                    "wh": dense(h, 4 * h),
                                    "b": ((4 * h,), 0.0)}
        d_in = 2 * h
    dnn = cfg["dnn_dim"]
    p["dnn0"] = {"w": dense(d_in, dnn), "b": ((dnn,), 0.0)}
    p["dnn1"] = {"w": dense(dnn, dnn), "b": ((dnn,), 0.0)}
    p["pred_embed"] = {"w": ((cfg["vocab_size"], cfg["pred_embed"]), 1.0)}
    hp = cfg["pred_hidden"]
    p["pred_gru"] = {"wx": dense(cfg["pred_embed"], 3 * hp),
                     "wh": dense(hp, 3 * hp), "b": ((3 * hp,), 0.0)}
    j = cfg["joint_dim"]
    p["joint"] = {"w_enc": dense(dnn, j), "w_pred": dense(hp, j),
                  "w_out": dense(j, cfg["vocab_size"])}
    return p


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _lstm(p, x, reverse):
    d_h = p["wh"].shape[0]
    xw = x @ p["wx"] + p["b"]

    def step(carry, xt):
        h, c = carry
        z = xt + h @ p["wh"]
        i, f, g, o = (z[:, k * d_h:(k + 1) * d_h] for k in range(4))
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    h0 = jnp.zeros((x.shape[0], d_h), x.dtype)
    _, hs = jax.lax.scan(step, (h0, h0), jnp.swapaxes(xw, 0, 1),
                         reverse=reverse)
    return jnp.swapaxes(hs, 0, 1)


def _gru(p, x):
    d_h = p["wh"].shape[0]
    xw = x @ p["wx"] + p["b"]

    def step(h, xt):
        hw = h @ p["wh"]
        r = jax.nn.sigmoid(xt[:, :d_h] + hw[:, :d_h])
        z = jax.nn.sigmoid(xt[:, d_h:2 * d_h] + hw[:, d_h:2 * d_h])
        n = jnp.tanh(xt[:, 2 * d_h:] + r * hw[:, 2 * d_h:])
        h = (1.0 - z) * n + z * h
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros((x.shape[0], d_h), x.dtype),
                         jnp.swapaxes(xw, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def encode(p, cfg, feats):
    x = feats[..., None]
    for i in range(len(cfg["cnn_channels"])):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"], (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + p[f"conv{i}"]["b"])
    b, t, f, c = x.shape
    x = x.reshape(b, t, f * c)
    for i in range(cfg["lstm_layers"]):
        x = jnp.concatenate([_lstm(p[f"lstm{i}_f"], x, False),
                             _lstm(p[f"lstm{i}_b"], x, True)], axis=-1)
    x = jax.nn.relu(x @ p["dnn0"]["w"] + p["dnn0"]["b"])
    return jax.nn.relu(x @ p["dnn1"]["w"] + p["dnn1"]["b"])


def predict(p, tokens):
    emb = p["pred_embed"]["w"][tokens]
    emb = jnp.concatenate([jnp.zeros_like(emb[:, :1]), emb], axis=1)
    return _gru(p["pred_gru"], emb)


def transducer_nll(logits, tokens, t_len, u_len):
    """NLL of one utterance from its dense joint logits (T, U+1, V), by
    the forward variable over anti-diagonals ``t + u = n``."""
    T, U1, _ = logits.shape
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    blank = lp[:, :, 0]
    lab = jnp.concatenate([tokens, jnp.zeros((1,), tokens.dtype)])
    emit = jnp.take_along_axis(lp, jnp.broadcast_to(
        lab[None, :, None], (T, U1, 1)), axis=-1)[..., 0]
    u = jnp.arange(U1)
    emit = jnp.where(u[None, :] < u_len, emit, NEG)
    a0 = jnp.where(u == 0, 0.0, NEG)

    def step(a_prev, n):
        t = n - u
        ok = (t >= 0) & (t < T)
        above = a_prev + blank[jnp.clip(t - 1, 0, T - 1), u]
        above = jnp.where(t >= 1, above, NEG)
        head = jnp.full((1,), NEG)
        left_a = jnp.concatenate([head, a_prev[:-1]])
        left_e = jnp.concatenate(
            [head, emit[jnp.clip(t, 0, T - 1)[1:], u[:-1]]])
        left = jnp.where(u >= 1, left_a + left_e, NEG)
        a = jnp.where(ok, jnp.logaddexp(above, left), NEG)
        return a, a

    _, diag = jax.lax.scan(step, a0, jnp.arange(1, T + U1 - 1))
    diag = jnp.concatenate([a0[None], diag])
    tl = t_len - 1
    return -(diag[tl + u_len, u_len] + blank[tl, u_len])


def per_utterance_loss(p, cfg, batch):
    """(B,) transducer NLL over the label count, as the training loss."""
    enc = encode(p, cfg, batch["feats"])
    pred = predict(p, batch["tokens"])
    ze = enc @ p["joint"]["w_enc"]
    zp = pred @ p["joint"]["w_pred"]
    z = jnp.tanh(ze[:, :, None, :] + zp[:, None, :, :])
    logits = z @ p["joint"]["w_out"]
    t_len = jnp.maximum(batch["feat_lens"] // cfg["time_reduction"], 1)
    nll = jax.vmap(transducer_nll)(logits, batch["tokens"], t_len,
                                   batch["token_lens"])
    return nll / jnp.maximum(batch["token_lens"].astype(jnp.float32), 1.0)


def _cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype"))
def _block_value_grad(p, batch, w, *, cfg_items, dtype):
    cfg = dict(cfg_items)

    def weighted(p):
        pc, bc = _cast(p, dtype), _cast(batch, dtype)
        return jnp.sum(per_utterance_loss(pc, cfg, bc).astype(jnp.float32)
                       * w)
    val, g = jax.value_and_grad(weighted)(p)
    return val, _cast(g, jnp.float32)


def _items(cfg: dict):
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(cfg.items())
                 if isinstance(v, (int, float, list)))


def block_size(cfg: dict, T: int, U1: int) -> int:
    per = (-(-T // 4)) * U1 * cfg["vocab_size"] * 4
    return max(1, int(BLOCK_BYTES // per))


def loss_and_grad(p, cfg, batch, weights, dtype=jnp.float32):
    """Weighted mean loss of a batch and its gradient, in blocks."""
    n = batch["feat_lens"].shape[0]
    bs = block_size(cfg, batch["feats"].shape[1],
                    batch["tokens"].shape[1] + 1)
    while n % bs:
        bs -= 1
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for s in range(0, n, bs):
            blk = {k: v[s:s + bs] for k, v in batch.items()}
            val, g = _block_value_grad(p, blk, weights[s:s + bs],
                                       cfg_items=_items(cfg), dtype=dtype)
            total += float(val)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    wsum = float(jnp.sum(weights))
    return total / wsum, jax.tree.map(lambda g: g / wsum, grads)


def clip(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw(p, g, state, opt: dict):
    """One AdamW step with bias correction; ``state`` is (step, m, v)."""
    step, m, v = state
    step += 1
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        u = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        return p - opt["lr"] * (u + opt["weight_decay"] * p)
    return jax.tree.map(upd, p, m, v), (step, m, v)


def train_steps(p0, cfg, batches, opt: dict, dtype=jnp.float32):
    """The reference's first steps from ``p0`` on ``batches`` (each a
    (batch, unit weights per utterance) pair).  Returns the losses, the
    first step's clipped gradient and the parameters after the last."""
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p, state, losses, g1 = p0, (0, zeros, zeros), [], None
    for batch, w in batches:
        loss, g = loss_and_grad(p, cfg, batch, w, dtype)
        g = clip(g, opt["grad_clip"])
        g1 = g if g1 is None else g1
        losses.append(loss)
        p, state = adamw(p, g, state, opt)
    return losses, g1, p
