"""Operations that the Conformer transducer needs, and its parameter count,
from a configuration file's numbers (never the program's).

Counted as ``flops.py`` counts the CRDNN: multiply-adds count two
operations, elementwise work is left out, recomputation is not counted,
and a training step is three forward passes.  Attention counts the
``T' x T'`` pairs it needs for each of its three contractions (content
scores, position scores, values), and the projection of the ``2T' - 1``
relative positions.
"""
from __future__ import annotations


def encoder_frames(cfg: dict, t_frames: int) -> int:
    """Frames after the two stride-2 convolutions: ``ceil(ceil(T/2)/2)``."""
    return -(-(-(-t_frames // 2)) // 2)


def forward_flops(cfg: dict, t_frames: int, u_len: int) -> float:
    """One utterance of ``t_frames`` input frames and ``u_len`` labels
    through the subsampling, the blocks, the prediction LSTM and the joint
    network with its vocabulary projection."""
    f, c, d = cfg["n_feats"], cfg["subsample_channels"], cfg["conformer_dim"]
    ff, k = cfg["conformer_ffn_dim"], cfg["conformer_kernel"]
    t1 = -(-t_frames // 2)
    t = encoder_frames(cfg, t_frames)
    flops = 2.0 * t1 * (f // 2) * c * 9
    flops += 2.0 * t * (f // 4) * c * 9 * c
    flops += 2.0 * t * c * (f // 4) * d
    block = 2 * 2.0 * t * d * ff * 2                  # two FFNs
    block += 4 * 2.0 * t * d * d                      # q, k, v, out
    block += 2.0 * (2 * t - 1) * d * d                # relative positions
    block += 3 * 2.0 * t * t * d                      # scores, values
    block += 2.0 * t * d * (2 * d + k + d)            # conv module
    flops += cfg["conformer_blocks"] * block
    u1 = u_len + 1
    e, hp, j = cfg["pred_embed"], cfg["pred_hidden"], cfg["joint_dim"]
    flops += 2.0 * u1 * (e + hp) * 4 * hp
    flops += 2.0 * t * d * j + 2.0 * u1 * hp * j
    flops += 2.0 * t * u1 * j * cfg["vocab_size"]
    return flops


def train_flops(cfg: dict, feat_lens, token_lens) -> float:
    """Forward and backward over utterances at their real lengths."""
    return sum(3.0 * forward_flops(cfg, int(t), int(u))
               for t, u in zip(feat_lens, token_lens))


def n_params(cfg: dict) -> int:
    """Trained parameters (batch norm's running statistics are not)."""
    f, c, d = cfg["n_feats"], cfg["subsample_channels"], cfg["conformer_dim"]
    ff, k, h = (cfg["conformer_ffn_dim"], cfg["conformer_kernel"],
                cfg["conformer_heads"])
    n = (9 * c + c) + (9 * c * c + c) + (c * (f // 4) * d + d)
    ln = 2 * d
    ffn = ln + d * ff + ff + ff * d + d
    mhsa = ln + 4 * (d * d + d) + d * d + 2 * h * (d // h)
    conv = ln + d * 2 * d + 2 * d + k * d + d + 2 * d + d * d + d
    n += cfg["conformer_blocks"] * (2 * ffn + mhsa + conv + ln)
    e, hp, j, v = (cfg["pred_embed"], cfg["pred_hidden"], cfg["joint_dim"],
                   cfg["vocab_size"])
    n += v * e + (e + hp) * 4 * hp + 4 * hp
    n += (d + hp) * j + j * v
    return n
