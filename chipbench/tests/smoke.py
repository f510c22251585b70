"""A checkout at a size the CPU runs in seconds: the benchmark's own
``BENCHMARK.json`` and traffic files, with the configuration cut to smoke
widths and the corpus to 64 short utterances.  The limits stay the
cells' own."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SMOKE_WIDTHS = {"n_feats": 8, "cnn_channels": [4, 8], "lstm_layers": 1,
                "lstm_hidden": 16, "dnn_dim": 32, "pred_embed": 16,
                "pred_hidden": 16, "joint_dim": 32, "vocab_size": 37}
SMOKE_CORPUS = {"n_utts": 64, "duration_min_s": 0.05,
                "duration_max_s": 0.6, "tokens_per_s": 10.0}


#: the control's test (``test_control.py``) runs at smoke widths on the
#: corpus's own durations: the bfloat16 control's gradient departs from
#: the reference's by its long recurrences, as at the cell's own size
CONTROL_SIZE = {"widths": SMOKE_WIDTHS,
                "corpus": {"n_utts": 64, "duration_min_s": 1.0}}


def make_root(tmp: str, widths=None, corpus=None) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(widths or SMOKE_WIDTHS)
        c["file"] = c["name"] + ".json"
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(cfg, f)
    for w in bench["workloads"]:
        name = w["traffic"] + ".json"
        with open(os.path.join(REPO, "chipbench", "traffic", name)) as f:
            tr = json.load(f)
        tr["corpus"].update(corpus or SMOKE_CORPUS)
        tr["batch_units"] = 4
        with open(os.path.join(tmp, "chipbench", "traffic", name), "w") as f:
            json.dump(tr, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def copy_benchmark_only(dst: str) -> str:
    """A directory holding only ``BENCHMARK.json`` and ``chipbench/``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    return dst
