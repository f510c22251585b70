"""The readers of the Conformer's scopes (``conformer_us_per_frame.asr``,
``mhsa_us_per_frame.asr``) on a hand-built trace summary and scope map,
as ``test_layer_readers.py`` checks the CRDNN's: forward, backward and
rematerialized ops all count, other scopes do not, and both are silent
where the program keeps no map for the window."""
import pytest

from chipbench import bench, trace
from repro import obs

BODY = "jit(run)/while/body/closed_call/cond/branch_1_fun"
SCOPES = {
    "%fusion.1": BODY + "/jvp()/while/body/closed_call/conformer.mhsa/dot",
    "%fusion.2": BODY + "/transpose(jvp())/while/body/closed_call/checkpoint"
                 "/rematted_computation/conformer.mhsa/exp",
    "%fusion.3": BODY + "/jvp()/while/body/closed_call/conformer.ffn/dot",
    "%fusion.4": BODY + "/transpose(jvp(conformer.subsample))/conv",
    "%fusion.5": BODY + "/transpose(jvp())/while/body/closed_call/checkpoint"
                 "/conformer.conv/mul",
    "%fusion.6": BODY + "/jvp(rnnt_loss.fwd)/while/body/add",
    "%while.7": BODY + "/jvp()/while",
}
#: device seconds of each op in the window, summed over two devices
OP_S = {"%fusion.1": 4.0, "%fusion.2": 2.0, "%fusion.3": 1.0,
        "%fusion.4": 0.5, "%fusion.5": 0.25, "%fusion.6": 8.0,
        "%while.7": 30.0, "%copy.8": 1.0}
FRAMES = 1.0e6


def _hlo(scopes):
    lines = ["HloModule jit_run, is_scheduled=true", "",
             "ENTRY %main.9 (p: f32[2]) -> f32[2] {"]
    for name, path in scopes.items():
        lines.append(f'  {name} = f32[2]{{0}} fusion(f32[2]{{0}} %p), '
                     f'kind=kLoop, metadata={{op_name="{path}" '
                     f'source_file="x.py" source_line=1}}')
    lines.append("}")
    return "\n".join(lines)


def _run():
    summary = trace.Summary(window_s=5.0, busy_s=5.0, n_devices=2,
                            op_s=dict(OP_S), op_count={k: 1 for k in OP_S},
                            module_s={}, module_count={}, gaps=[])
    window = bench.Window(seconds=5.0, units=1, failed=0,
                          totals={"frames": FRAMES}, compiles=0)
    return bench.RunFacts(cell=None, window=window, facts={},
                          trace=summary, peaks={}, chips=2)


def _read(metric, run):
    return bench._load_reader(metric)(run)


def test_conformer_scopes_per_frame():
    module = obs.register_module(_hlo(SCOPES))
    obs.record_dispatch(obs.Dispatch(module, False, 24, 19, 1000, 300))
    run = _run()
    # per device: (4 + 2 + 1 + 0.5 + 0.25) / 2 s of encoder, (4 + 2) / 2
    # of attention; the loss, the container and the unscoped copy are out
    assert _read("conformer_us_per_frame.asr", run) == pytest.approx(
        1e6 * 3.875 / FRAMES)
    assert _read("mhsa_us_per_frame.asr", run) == pytest.approx(
        1e6 * 3.0 / FRAMES)


def test_silent_without_a_map():
    obs.record_dispatch(obs.Dispatch("jit_run#never-registered", False,
                                     None, None, None, None))
    for metric in ("conformer_us_per_frame.asr", "mhsa_us_per_frame.asr"):
        assert _read(metric, _run()) is None
