"""Plain reference of the Conformer transducer (Gulati et al. 2020), written
from the model's description in straightforward ``jax.numpy``: forward,
transducer loss, gradient, clip, AdamW and batch norm's running
statistics after each step.

It imports nothing of the program under test; the transducer loss, the
prediction network's LSTM, the clip and AdamW are those of
``reference/crdnn.py``.  The function it computes, with the departures
from the paper that the configuration file lists under ``assumed``:

- subsampling: input frames past an utterance's length are zeroed and the
  time axis is zero-padded to a multiple of 4; two 3x3 stride-2 Conv2d
  (zero padding of one frame and one feature after), ReLU, and after each
  the frames past ``ceil(L/2)`` (then ``ceil(L/4)``) set to zero; then a
  linear map to the encoder width;
- each block: ``x + FFN/2``, ``+ MHSA``, ``+ Conv``, ``LN(x + FFN/2)``;
  every LayerNorm (eps 1e-5) and the batch norm scale by ``1 + g``;
- MHSA with Transformer-XL relative positions: for heads of width ``dh``,
  ``((q_i + u) k_j + (q_i + v) (R_{i-j} W_R)) / sqrt(dh)`` with ``R``
  the ``[sin | cos]`` encoding of ``i - j``, looked up per ``(i, j)``;
  keys past the utterance's live frames masked;
- conv module: LN, pointwise to ``2d``, GLU, frames past the live ones
  zeroed, depthwise conv with ``(K-1)//2`` zeros before and the rest
  after, batch norm over live frames of the whole batch (biased
  variance), Swish, pointwise;
- running statistics ``0.99 * running + 0.01 * batch``, eps 1e-3;
- the prediction network is an embedding and one LSTM layer (forget bias
  +1); the joint is ``tanh(enc W_enc + pred W_pred) W_out``;
- the lattice of an utterance is ``ceil(L/4)`` frames long.

Batch norm couples the utterances of a batch, so the encoder runs over
the whole batch at once, each block under ``jax.checkpoint``; the joint
and the loss run over blocks of utterances one after another
(``lax.map``), and their encoder cotangent is pulled back through one
``vjp`` of the encoder, all in one jitted call.  ``dtype`` is the
precision of the network (float32 at ``highest`` for the reference,
bfloat16 for the control); the lattice and the loss stay float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import crdnn

LN_EPS = 1e-5


def _dense(d_in, d_out):
    return ((d_in, d_out), 1.0 / np.sqrt(d_in))


def param_specs(cfg: dict) -> dict:
    """Shapes and init scales of every trained weight, as ``(shape, std)``;
    a std of 0 means zeros.  The tree is the program's trained parameter
    tree: block leaves stacked over the blocks."""
    n, d, f = cfg["conformer_blocks"], cfg["conformer_dim"], \
        cfg["conformer_ffn_dim"]
    h, k, c = cfg["conformer_heads"], cfg["conformer_kernel"], \
        cfg["subsample_channels"]

    def stack(spec):
        return jax.tree.map(lambda s: ((n,) + s[0], s[1]), spec,
                            is_leaf=lambda s: isinstance(s, tuple)
                            and isinstance(s[0], tuple))

    def zeros(*shape):
        return (shape, 0.0)

    def ffn():
        return {"ln_g": zeros(d), "ln_b": zeros(d), "w1": _dense(d, f),
                "b1": zeros(f), "w2": _dense(f, d), "b2": zeros(d)}

    mhsa = {"ln_g": zeros(d), "ln_b": zeros(d), "w_pos": _dense(d, d),
            "pos_u": zeros(h, d // h), "pos_v": zeros(h, d // h)}
    for name in "qkvo":
        mhsa[f"w{name}"], mhsa[f"b{name}"] = _dense(d, d), zeros(d)
    conv = {"ln_g": zeros(d), "ln_b": zeros(d), "pw1_w": _dense(d, 2 * d),
            "pw1_b": zeros(2 * d), "dw_w": ((k, d), 1.0 / np.sqrt(k)),
            "dw_b": zeros(d), "bn_g": zeros(d), "bn_b": zeros(d),
            "pw2_w": _dense(d, d), "pw2_b": zeros(d)}
    blocks = stack({"ffn1": ffn(), "mhsa": mhsa, "conv": conv,
                    "ffn2": ffn(), "ln_g": zeros(d), "ln_b": zeros(d)})
    sub = {"conv0": {"w": ((3, 3, 1, c), 1.0 / 3.0), "b": zeros(c)},
           "conv1": {"w": ((3, 3, c, c), 1.0 / np.sqrt(9.0 * c)),
                     "b": zeros(c)},
           "proj": {"w": _dense(c * (cfg["n_feats"] // 4), d),
                    "b": zeros(d)}}
    e, hp, j = cfg["pred_embed"], cfg["pred_hidden"], cfg["joint_dim"]
    return {"subsample": sub, "blocks": blocks,
            "pred_embed": {"w": ((cfg["vocab_size"], e), 1.0)},
            "pred_lstm": {"wx": _dense(e, 4 * hp), "wh": _dense(hp, 4 * hp),
                          "b": zeros(4 * hp)},
            "joint": {"w_enc": _dense(d, j), "w_pred": _dense(hp, j),
                      "w_out": _dense(j, cfg["vocab_size"])}}


def init_stats(cfg: dict) -> dict:
    shape = (cfg["conformer_blocks"], cfg["conformer_dim"])
    return {"mean": jnp.zeros(shape), "var": jnp.ones(shape)}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * (1.0 + g) + b


def _frames_live(lens, t, dtype=jnp.float32):
    return (jnp.arange(t)[None, :] < lens[:, None]).astype(dtype)


def encoder_lengths(feat_lens):
    half = (feat_lens + 1) // 2
    return jnp.maximum((half + 1) // 2, 1)


def subsample(p, feats, lens):
    t = feats.shape[1]
    x = feats * _frames_live(lens, t, feats.dtype)[..., None]
    x = jnp.concatenate(
        [x, jnp.zeros((x.shape[0], (4 - t % 4) % 4, x.shape[2]), x.dtype)],
        axis=1)[..., None]
    for i in range(2):
        x = jax.lax.conv_general_dilated(
            x, p[f"conv{i}"]["w"], (2, 2), ((0, 1), (0, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        lens = (lens + 1) // 2
        x = jax.nn.relu(x + p[f"conv{i}"]["b"]) \
            * _frames_live(lens, x.shape[1], x.dtype)[..., None, None]
    b, t4, f4, c = x.shape
    return x.reshape(b, t4, f4 * c) @ p["proj"]["w"] + p["proj"]["b"]


def _ffn(p, x):
    h = _ln(x, p["ln_g"], p["ln_b"]) @ p["w1"] + p["b1"]
    return (h * jax.nn.sigmoid(h)) @ p["w2"] + p["b2"]


def _sinusoid(rel, d):
    """[sin | cos] encoding of relative positions ``rel`` (any shape)."""
    inv = 10000.0 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = rel[..., None].astype(jnp.float32) * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _mhsa(p, x, live, n_heads):
    b, t, d = x.shape
    dh = d // n_heads
    h = _ln(x, p["ln_g"], p["ln_b"])
    heads = lambda y: y.reshape(b, t, n_heads, dh)
    q = heads(h @ p["wq"] + p["bq"])
    k = heads(h @ p["wk"] + p["bk"])
    v = heads(h @ p["wv"] + p["bv"])
    # the encoding of every distinct i - j, projected, then looked up
    rel = jnp.arange(-(t - 1), t)
    r = (_sinusoid(rel, d).astype(x.dtype) @ p["w_pos"]).reshape(
        2 * t - 1, n_heads, dh)
    ij = jnp.arange(t)[:, None] - jnp.arange(t)[None, :] + (t - 1)
    content = jnp.einsum("bihe,bjhe->bhij", q + p["pos_u"], k)
    qv = q + p["pos_v"]                                     # (b,t,H,dh)
    position = jnp.einsum("bihe,ijhe->bhij", qv, r[ij])
    s = (content + position) / np.sqrt(dh)
    s = jnp.where(live[:, None, None, :] > 0, s, -jnp.inf)
    a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
    o = jnp.einsum("bhij,bjhe->bihe", a, v).reshape(b, t, d)
    return o @ p["wo"] + p["bo"]


def _conv(p, x, live, eps):
    d = x.shape[-1]
    h = _ln(x, p["ln_g"], p["ln_b"]) @ p["pw1_w"] + p["pw1_b"]
    h = h[..., :d] * jax.nn.sigmoid(h[..., d:]) * live[..., None].astype(
        h.dtype)
    k = p["dw_w"].shape[0]
    lo = (k - 1) // 2
    hp = jnp.pad(h, ((0, 0), (lo, k - 1 - lo), (0, 0)))
    t = h.shape[1]
    h = sum(hp[:, i:i + t] * p["dw_w"][i] for i in range(k)) + p["dw_b"]
    # batch norm over the live frames of the whole batch
    m = live[..., None].astype(jnp.float32)
    n = m.sum()
    h32 = h.astype(jnp.float32)
    mean = (h32 * m).sum((0, 1)) / n
    var = (((h32 - mean) ** 2) * m).sum((0, 1)) / n
    h = (h - mean.astype(h.dtype)) / jnp.sqrt(var.astype(h.dtype) + eps)
    h = h * (1.0 + p["bn_g"]) + p["bn_b"]
    h = h * jax.nn.sigmoid(h)
    return h @ p["pw2_w"] + p["pw2_b"], (mean, var)


def _block(x, p, live, cfg):
    x = x + 0.5 * _ffn(p["ffn1"], x)
    x = x + _mhsa(p["mhsa"], x, live, cfg["conformer_heads"])
    c, stats = _conv(p["conv"], x, live, cfg["bn_eps"])
    x = x + c
    return _ln(x + 0.5 * _ffn(p["ffn2"], x), p["ln_g"], p["ln_b"]), stats


def encode(p, cfg, feats, lens):
    """Training forward of the whole batch: -> (enc, per-block batch
    statistics (mean, var), each (blocks, d))."""
    x = jax.checkpoint(subsample)(p["subsample"], feats, lens)
    live = _frames_live(encoder_lengths(lens), x.shape[1])
    block = jax.checkpoint(functools.partial(_block, live=live, cfg=cfg))
    x, (means, variances) = jax.lax.scan(block, x, p["blocks"])
    return x, (means, variances)


# ---------------------------------------------------------------------------
# loss, gradient, step
# ---------------------------------------------------------------------------

ENCODER_KEYS = ("subsample", "blocks")


def _tail_loss(enc, pt, batch, w):
    """Weighted loss sum of a block of utterances from their encoder
    frames, through the prediction and joint networks."""
    emb = pt["pred_embed"]["w"][batch["tokens"]]
    emb = jnp.concatenate([jnp.zeros_like(emb[:, :1]), emb], axis=1)
    pred = crdnn._lstm(pt["pred_lstm"], emb, False)
    ze = enc @ pt["joint"]["w_enc"]
    zp = pred @ pt["joint"]["w_pred"]
    z = jnp.tanh(ze[:, :, None, :] + zp[:, None, :, :])
    logits = z @ pt["joint"]["w_out"]
    nll = jax.vmap(crdnn.transducer_nll)(
        logits, batch["tokens"], encoder_lengths(batch["feat_lens"]),
        batch["token_lens"])
    per = nll / jnp.maximum(batch["token_lens"].astype(jnp.float32), 1.0)
    return jnp.sum(per.astype(jnp.float32) * w)


@functools.partial(jax.jit, static_argnames=("cfg_items", "dtype", "block"))
def _value_grad(p, batch, w, *, cfg_items, dtype, block):
    cfg = dict(cfg_items)
    p_enc = {k: p[k] for k in ENCODER_KEYS}
    p_tail = {k: v for k, v in p.items() if k not in ENCODER_KEYS}

    def fwd(pe):
        return encode(crdnn._cast(pe, dtype), cfg,
                      batch["feats"].astype(dtype), batch["feat_lens"])
    enc, pull, stats = jax.vjp(fwd, p_enc, has_aux=True)
    n = enc.shape[0]
    split = lambda a: a.reshape((n // block, block) + a.shape[1:])
    rest = {k: split(v) for k, v in batch.items() if k != "feats"}

    def one(args):
        e, b, wb = args
        return jax.value_and_grad(
            lambda e, pt: _tail_loss(e, crdnn._cast(pt, dtype), b, wb),
            argnums=(0, 1))(e, p_tail)
    vals, (g_enc, g_tail) = jax.lax.map(one, (split(enc), rest, split(w)))
    (g_enc_p,) = pull(g_enc.reshape(enc.shape))
    grads = dict(jax.tree.map(lambda g: g.sum(0), g_tail), **g_enc_p)
    return (vals.sum(), crdnn._cast(grads, jnp.float32),
            jax.tree.map(lambda a: a.astype(jnp.float32), stats))


def loss_and_grad(p, cfg, batch, weights, dtype=jnp.float32):
    """Weighted mean loss of a batch, its gradient, and the batch
    statistics (mean, var) of each block's batch norm.  The joint and the
    loss take ``crdnn.block_size`` utterances at a time."""
    n = batch["feat_lens"].shape[0]
    bs = crdnn.block_size(cfg, batch["feats"].shape[1],
                          batch["tokens"].shape[1] + 1)
    while n % bs:
        bs -= 1
    with jax.default_matmul_precision("highest"):
        total, grads, stats = _value_grad(p, batch, weights,
                                          cfg_items=crdnn._items(cfg),
                                          dtype=dtype, block=bs)
    wsum = float(jnp.sum(weights))
    return (float(total) / wsum, jax.tree.map(lambda g: g / wsum, grads),
            stats)


def train_steps(p0, cfg, batches, opt: dict, dtype=jnp.float32):
    """The reference's first steps from ``p0`` on ``batches`` (each a
    (batch, unit weights per utterance) pair).  Returns the losses, the
    first step's clipped gradient, the parameters after the last step and
    the running statistics after it."""
    zeros = jax.tree.map(jnp.zeros_like, p0)
    p, state, losses, g1 = p0, (0, zeros, zeros), [], None
    run = init_stats(cfg)
    m = cfg["bn_momentum"]
    for batch, w in batches:
        loss, g, (mean, var) = loss_and_grad(p, cfg, batch, w, dtype)
        run = {"mean": m * run["mean"] + (1.0 - m) * mean,
               "var": m * run["var"] + (1.0 - m) * var}
        g = crdnn.clip(g, opt["grad_clip"])
        g1 = g if g1 is None else g1
        losses.append(loss)
        p, state = crdnn.adamw(p, g, state, opt)
    return losses, g1, p, run
