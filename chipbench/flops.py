"""Operations and bytes that the algorithms need, from their shapes.

Every count is of required work: multiply-adds count two operations,
elementwise work inside layers is left out, recomputation is not counted,
and a training step is three forward passes (forward, and a backward that
costs twice the forward).  These functions are the benchmark's yardstick:
they read the configuration file's numbers, never the program.
"""
from __future__ import annotations

import math


def _ceil_half(n: int) -> int:
    return -(-n // 2)


def crdnn_encoder_frames(cfg: dict, t_frames: int) -> int:
    """Frames after the CRDNN's stride-2, stride-2 CNN ('SAME' padding)."""
    t = t_frames
    for _ in cfg["cnn_channels"]:
        t = _ceil_half(t)
    return t


def crdnn_forward_flops(cfg: dict, t_frames: int, u_len: int) -> float:
    """One utterance of ``t_frames`` input frames and ``u_len`` labels
    through the CRDNN transducer: CNN, bi-LSTM, DNN, prediction GRU and
    the joint network with its vocabulary projection."""
    f = cfg["n_feats"]
    t, c_in, flops = t_frames, 1, 0.0
    for c in cfg["cnn_channels"]:
        t, f = _ceil_half(t), _ceil_half(f)
        flops += 2.0 * t * f * c * 9 * c_in
        c_in = c
    h = cfg["lstm_hidden"]
    d_in = cfg["cnn_channels"][-1] * (cfg["n_feats"] // 4)
    for _ in range(cfg["lstm_layers"]):
        flops += 2 * t * (2.0 * d_in * 4 * h + 2.0 * h * 4 * h)
        d_in = 2 * h
    dnn, j, v = cfg["dnn_dim"], cfg["joint_dim"], cfg["vocab_size"]
    flops += 2.0 * t * (d_in * dnn + dnn * dnn)
    u1 = u_len + 1
    e, hp = cfg["pred_embed"], cfg["pred_hidden"]
    flops += 2.0 * u1 * (e * 3 * hp + hp * 3 * hp)
    flops += 2.0 * t * dnn * j + 2.0 * u1 * hp * j
    flops += 2.0 * t * u1 * j * v
    return flops


def crdnn_train_flops(cfg: dict, feat_lens, token_lens) -> float:
    """Forward and backward over utterances at their real lengths."""
    return sum(3.0 * crdnn_forward_flops(cfg, int(t), int(u))
               for t, u in zip(feat_lens, token_lens))


def lattice_flops_bytes(t: int, b: int, u1: int):
    """One call of the transducer lattice kernel on (t, b, u1) rows: three
    fp32 inputs read and one fp32 output written; per element one
    log-add-exp for the blank move and one per doubling step of the row
    scan, at seven elementwise operations each."""
    steps = 1 + math.ceil(math.log2(max(u1, 2)))
    return 7.0 * steps * t * b * u1, 16.0 * t * b * u1
