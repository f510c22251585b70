"""Conformer(L) transducer [Gulati et al. 2020, "Conformer: Convolution-
augmented Transformer for Speech Recognition", Table 1].

Encoder: convolutional subsampling by 4 (two 3x3 stride-2 Conv2d of 512
channels), 17 Conformer blocks of width 512 (8 attention heads of 64 with
Transformer-XL relative positions, FFN 2,048, depthwise conv kernel 32,
batch norm).  Prediction network: 640-d embedding, one LSTM layer of 640.
Joint 640 into 1,000 BPE units.  Dropout and SpecAugment are off; the
assumed widths are listed in ``chipbench/configs/conformer_l_transducer.json``.
"""
from repro.configs.base import ModelConfig, RNNTConfig

CONFIG = ModelConfig(
    name="conformer-l-transducer",
    family="rnnt",
    n_layers=17,                 # Conformer blocks (descriptive)
    d_model=512,
    n_heads=8,
    n_kv_heads=8,
    head_dim=64,
    d_ff=2048,
    vocab_size=1000,
    rnnt=RNNTConfig(encoder="conformer", predictor="lstm",
                    conformer_blocks=17, conformer_dim=512,
                    conformer_heads=8, conformer_ffn_dim=2048,
                    conformer_kernel=32, subsample_channels=512,
                    pred_embed=640, pred_hidden=640, joint_dim=640,
                    vocab_size=1000),
)
