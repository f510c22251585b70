"""The ``train_by_config`` driver's cells end to end on the CPU at smoke
size: ``conformer.train_subset`` at the Conformer's smoke widths, and
``crdnn.train_subset.x4`` on a 4x1 mesh of four host devices (in a
subprocess, which sets them up before JAX starts).  Each run of the
program is correct; the control and each fault of ``faults.py``, planted
in the program, make it not correct, under the cells' own limits."""
import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from chipbench import bench, faults
from chipbench.tests import smoke

#: the Conformer's smoke widths (``configs/base.py:reduce_for_smoke``)
CONFORMER_WIDTHS = {"n_feats": 8, "conformer_blocks": 2, "conformer_dim": 32,
                    "conformer_heads": 4, "conformer_ffn_dim": 64,
                    "conformer_kernel": 4, "subsample_channels": 8}
PLANTS = {"program": None, "control": faults.control, **faults.FAULTS["train"]}


def make_root(tmp: str) -> str:
    """``smoke.make_root``'s checkout, with the Conformer's configuration
    file cut to its smoke widths and the x4 subset split into 4
    partitions (the smoke corpus has 64 utterances)."""
    root = smoke.make_root(tmp)
    # the Conformer's own file at its smoke widths (``smoke.make_root``
    # writes the CRDNN's smoke widths into every configuration)
    with open(os.path.join(smoke.REPO, "chipbench", "configs",
                           "conformer_l_transducer.json")) as f:
        cfg = json.load(f)
    cfg.update(CONFORMER_WIDTHS, vocab_size=smoke.SMOKE_WIDTHS["vocab_size"],
               **{k: smoke.SMOKE_WIDTHS[k]
                  for k in ("pred_embed", "pred_hidden", "joint_dim")})
    path = os.path.join(root, "conformer-l-transducer.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    path = os.path.join(root, "chipbench", "traffic",
                        "asr_train_subset_x4.json")
    with open(path) as f:
        tr = json.load(f)
    tr["n_partitions"] = 4
    with open(path, "w") as f:
        json.dump(tr, f)
    return root


def run_cell(root, cell, plant, seed=2 ** 33 + 7) -> dict:
    t0 = time.perf_counter()

    def go():
        return bench.run(cell, seed, 0.2, False, t_start=t0, root=root,
                         require_accelerator=False, compile_cache=False,
                         log=lambda s: None)
    if PLANTS[plant] is None:
        return go()
    with PLANTS[plant]():
        return go()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout on the smoke corpus.  The bfloat16 control departs there
    as on the chip: batch norm's bfloat16 arithmetic leaves the depthwise
    bias a gradient that float32 keeps at round-off (PERF.md, section
    4)."""
    return make_root(str(tmp_path_factory.mktemp("by_config")))


@pytest.mark.parametrize("plant", sorted(PLANTS))
def test_conformer_cell(root, plant):
    result = run_cell(root, "conformer.train_subset", plant)
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert {"stats_gap", "inert_grad_gap"} <= set(result["checks"])
    assert set(result["metrics"]) == {"asr_train_frames_per_s", "setup_s"}
    assert result["correct"] == (plant == "program"), result["checks"]


X4 = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{repo!r}, {src!r}]
    from chipbench.tests import test_by_config as t
    root = t.make_root({tmp!r})
    out = {{}}
    for plant in sorted(t.PLANTS):
        r = t.run_cell(root, "crdnn.train_subset.x4", plant)
        out[plant] = {{"correct": r["correct"], "failed": r["failed"],
                       "device": r["device"]["count"],
                       "checks": {{k: c["value"]
                                  for k, c in r["checks"].items()}}}}
    print(json.dumps(out))
""")


def test_x4_cell_on_four_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = X4.format(repo=smoke.REPO, src=os.path.join(smoke.REPO, "src"),
                     tmp=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    for plant, r in got.items():
        assert r["device"] == 4 and r["failed"] == 0
        assert r["checks"]["compiles_in_window"] == 0
        assert "stats_gap" not in r["checks"]
        assert r["correct"] == (plant == "program"), (plant, r)


def test_inert_gap_reads_only_the_inert_leaves():
    """The leaves under a thousandth of the median reference norm are read
    against the median; the others, however far off, are not."""
    from chipbench.drivers.train_by_config import inert_gap
    want = [1.0, 2.0, 3.0, 0.5, 1e-8]
    assert inert_gap([1.5, 2.0, 3.0, 0.1, 1e-8], want) == 0.0
    assert abs(inert_gap([1.0, 2.0, 3.0, 0.5, 0.005], want) - 0.005) < 1e-7
    assert inert_gap([1.0, 2.0], [1.0, 2.0]) == 0.0
