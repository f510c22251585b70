"""The benchmark's operation and byte counts against values reckoned by
hand at one small shape, and the peaks table."""
import pytest

from chipbench import flops, peaks

TINY_CRDNN = {"n_feats": 8, "cnn_channels": [4, 8], "lstm_layers": 1,
              "lstm_hidden": 2, "dnn_dim": 3, "pred_embed": 2,
              "pred_hidden": 2, "joint_dim": 3, "vocab_size": 5}


def test_crdnn_forward():
    # 8 frames, 2 labels: conv 1152 + 2304, bi-LSTM 1152, DNN 84,
    # GRU 144, joint projections 72, vocabulary 180
    assert flops.crdnn_encoder_frames(TINY_CRDNN, 8) == 2
    assert flops.crdnn_encoder_frames(TINY_CRDNN, 9) == 3
    assert flops.crdnn_forward_flops(TINY_CRDNN, 8, 2) == 5088


def test_crdnn_train():
    assert flops.crdnn_train_flops(TINY_CRDNN, [8, 8], [2, 2]) == 2 * 15264


def test_lattice():
    # 1 + log2(4) log-add-exps of 7 operations on 40 cells; 4 fp32 arrays
    assert flops.lattice_flops_bytes(5, 2, 4) == (840, 640)


def test_peaks_known_and_unknown():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9000")
