"""The readers of the program's scopes and dispatch records
(``loss_us_per_frame.asr``, ``lstm_us_per_frame.asr``,
``live_frame_share.asr_train``) on a hand-built trace summary and a
hand-built registry in ``repro.obs``: their values, their silence where
the program keeps nothing for the window, the raise on an op name that
two of the window's modules map to different scopes, and that only the
window's own dispatch records count."""
import sys

import pytest

import repro
from chipbench import bench, trace
from repro import obs

LOSS_FWD = "jit(run)/while/body/closed_call/jvp(rnnt_loss.fwd)/while/body"
LOSS_BWD = "jit(run)/while/body/closed_call/transpose(jvp(rnnt_loss.bwd))"
LSTM = "jit(run)/while/body/closed_call/jvp(encoder_lstm)/while/body/dot"
LSTM_BWD = "jit(run)/while/body/closed_call/transpose(jvp(encoder_lstm))"
OPT = "jit(run)/while/body/closed_call/optimizer/mul"
#: device seconds of each op in the window, summed over two devices
OP_S = {"%fusion.1": 4.0, "%fusion.2": 2.0, "%rnnt_lattice.3": 0.5,
        "%while.4": 9.0, "%fusion.5": 1.5, "%dot.6": 0.25, "%fusion.7": 1.0,
        "%copy.8": 0.75}
SCOPES = {"%fusion.1": LOSS_FWD + "/add", "%fusion.2": LOSS_BWD + "/exp",
          "%rnnt_lattice.3": LOSS_FWD + "/pallas_call",
          "%while.4": LOSS_BWD + "/while",     # a container: left out
          "%fusion.5": LSTM, "%dot.6": LSTM_BWD + "/dot_general",
          "%fusion.7": OPT}                    # %copy.8 has no scope
FRAMES = 2.0e6


def _hlo(scopes, module="jit_run"):
    lines = [f"HloModule {module}, is_scheduled=true", "",
             "ENTRY %main.9 (p: f32[2]) -> f32[2] {"]
    for name, path in scopes.items():
        lines.append(f'  {name} = f32[2]{{0}} fusion(f32[2]{{0}} %p), '
                     f'kind=kLoop, metadata={{op_name="{path}" '
                     f'source_file="x.py" source_line=1}}')
    lines.append("}")
    return "\n".join(lines)


def _record(module, live_positions=300, positions=1000):
    obs.record_dispatch(obs.Dispatch(module, False, 24, 19, positions,
                                     live_positions))


def _run(units=2):
    summary = trace.Summary(window_s=5.0, busy_s=5.0, n_devices=2,
                            op_s=dict(OP_S), op_count={k: 1 for k in OP_S},
                            module_s={}, module_count={}, gaps=[])
    window = bench.Window(seconds=5.0, units=units, failed=0,
                          totals={"frames": FRAMES}, compiles=0)
    return bench.RunFacts(cell=None, window=window, facts={},
                          trace=summary, peaks={}, chips=2)


def _read(metric, run):
    return bench._load_reader(metric)(run)


def test_scoped_device_time_per_frame():
    module = obs.register_module(_hlo(SCOPES))
    for _ in range(2):
        _record(module)
    run = _run()
    # per device: (4 + 2 + 0.5) / 2 s of loss, (1.5 + 0.25) / 2 s of LSTM
    assert _read("loss_us_per_frame.asr", run) == pytest.approx(
        1e6 * 3.25 / FRAMES)
    assert _read("lstm_us_per_frame.asr", run) == pytest.approx(
        1e6 * 0.875 / FRAMES)


def test_silent_without_a_map_or_records(monkeypatch):
    _record(None)
    _record("jit_run#never-registered")
    for metric in ("loss_us_per_frame.asr", "lstm_us_per_frame.asr"):
        assert _read(metric, _run()) is None
    obs.record_dispatch(obs.Dispatch(None, False, None, None, None, None))
    assert _read("live_frame_share.asr_train", _run(units=1)) is None
    # a program without repro.obs, as before it was written
    module = obs.register_module(_hlo(SCOPES))
    for _ in range(2):
        _record(module)
    assert _read("live_frame_share.asr_train", _run()) == 30.0
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for metric in ("loss_us_per_frame.asr", "lstm_us_per_frame.asr",
                   "live_frame_share.asr_train"):
        assert _read(metric, _run()) is None


def test_an_op_two_modules_scope_differently_raises():
    a = obs.register_module(_hlo(SCOPES))
    b = obs.register_module(_hlo(dict(SCOPES, **{"%fusion.7": LSTM})))
    _record(a)
    _record(b)
    with pytest.raises(ValueError, match="%fusion.7 maps to 2 scope paths"):
        _read("lstm_us_per_frame.asr", _run())
    # the same map under two keys is no ambiguity
    c = obs.register_module(_hlo(SCOPES))
    _record(a)
    _record(c)
    assert _read("loss_us_per_frame.asr", _run()) == pytest.approx(
        1e6 * 3.25 / FRAMES)


def test_live_share_reads_only_the_window_records():
    module = obs.register_module(_hlo(SCOPES))
    for _ in range(3):      # set-up's check steps: mostly padding
        _record(module, live_positions=10, positions=1000)
    _record(module, live_positions=410, positions=1000)
    _record(module, live_positions=400, positions=1000)
    assert _read("live_frame_share.asr_train", _run(units=2)) == \
        pytest.approx(100.0 * 810 / 2000)
    assert _read("live_frame_share.asr_train", _run(units=5)) == \
        pytest.approx(100.0 * 840 / 5000)
