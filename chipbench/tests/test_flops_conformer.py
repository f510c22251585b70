"""The Conformer's operation and parameter counts: against values reckoned
by hand at one small shape, and the parameter count against the
program's own tree at the configuration's widths."""
import json
import os

import jax
import numpy as np

from chipbench import flops_conformer as fc
from chipbench.drivers import common
from chipbench.tests import smoke

TINY = {"n_feats": 8, "subsample_channels": 2, "conformer_blocks": 1,
        "conformer_dim": 4, "conformer_heads": 2, "conformer_ffn_dim": 8,
        "conformer_kernel": 3, "pred_embed": 2, "pred_hidden": 2,
        "joint_dim": 3, "vocab_size": 5}


def test_encoder_frames():
    assert [fc.encoder_frames(TINY, t) for t in (8, 9, 10, 13)] == \
        [2, 3, 3, 4]


def test_forward_at_a_small_shape():
    # 8 frames (T1 4, T' 2), 1 label: convs 576 + 288, projection 64;
    # one block: FFNs 512, projections 256, positions 96, attention 96,
    # conv module 240; LSTM 128; joint projections 48 + 24; vocabulary 120
    assert fc.forward_flops(TINY, 8, 1) == 2448
    assert fc.train_flops(TINY, [8, 8], [1, 1]) == 2 * 3 * 2448


def test_parameter_count_is_the_programs():
    with open(os.path.join(smoke.REPO, "chipbench", "configs",
                           "conformer_l_transducer.json")) as f:
        cfg = json.load(f)
    from repro.models.api import build_model
    from repro.models.common import split_state
    bundle = build_model(common.program_config(cfg))
    trained, _ = split_state(jax.eval_shape(bundle.init_params,
                                            jax.random.PRNGKey(0)))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(trained))
    assert fc.n_params(cfg) == n == cfg["n_params"]
    for widths in (TINY, {**cfg, **TINY}):
        small = dict(cfg, **widths)
        trained, _ = split_state(jax.eval_shape(
            build_model(common.program_config(small)).init_params,
            jax.random.PRNGKey(0)))
        assert fc.n_params(small) == sum(
            int(np.prod(a.shape)) for a in jax.tree.leaves(trained))
