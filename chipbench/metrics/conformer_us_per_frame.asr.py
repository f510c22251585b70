"""Device time of the Conformer encoder per real input frame, in
microseconds: the leaf ops of the traced window that the program's scope
map puts under ``conformer.subsample``, ``conformer.ffn``,
``conformer.mhsa`` or ``conformer.conv`` (``models/conformer.py``: the
forward, the backward and the blocks' recompute under remat), per
device, over the real frames the window trained.  The join, and when it
is silent or raises, are ``loss_us_per_frame.asr``'s.  A program whose
encoder has no such scope (the CRDNN) reads 0."""
import importlib.util
import os

SCOPE = r"conformer\.(?:subsample|ffn|mhsa|conv)"

_spec = importlib.util.spec_from_file_location(
    "chipbench_metric_loss_us_per_frame_asr",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "loss_us_per_frame.asr.py"))
_join = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_join)


def read(run):
    seconds = _join.scoped_seconds(run, SCOPE)
    if seconds is None:
        return None
    return 1e6 * seconds / run.window.totals["frames"]
