"""Device time of the fused transducer loss per real input frame, in
microseconds: the leaf ops of the traced window (containers left out, as
by ``trace.CONTAINER``) that the program's scope map puts under
``rnnt_loss.fwd`` or ``rnnt_loss.bwd`` (``core/rnnt_loss.py``, the
Pallas lattice calls included), per device, over the real frames the
window trained.

The join: the program (``repro.obs``) keeps, for each epoch executable
it compiled, its HLO instruction names with their ``op_name`` scope
paths, and one record per epoch dispatch naming the executable.  An op
of the trace is looked up in the maps of the executables that the
window's dispatches ran (the last ``window.units`` records).  A name
that two of them map to different paths is ambiguous, and raises.

Silent where the program keeps no scope maps or records: a program
without ``repro.obs``, or a window whose dispatches it did not record.
``lstm_us_per_frame.asr`` reads the same join for ``encoder_lstm``."""
import re

from chipbench import trace

#: the scope's name as a component of an op_name path, also inside a
#: transformation's parentheses: ``.../transpose(jvp(rnnt_loss.bwd))/...``
SCOPE = r"rnnt_loss\.(?:fwd|bwd)"


def scoped_seconds(run, scope: str):
    """Device seconds per device of the window's leaf ops under
    ``scope``, or None where the program has no map for the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    records = obs.dispatches()[-run.window.units:]
    modules = {r.module for r in records}
    maps = obs.scope_maps()
    if len(records) < run.window.units or not modules <= set(maps):
        return None
    rx = re.compile(r"(?:^|[/(])(?:%s)(?:[)/]|$)" % scope)
    seconds = 0.0
    for op, s in run.trace.op_s.items():
        if trace.CONTAINER.match(op):
            continue
        paths = {maps[m][op] for m in modules if op in maps[m]}
        if len(paths) > 1:
            raise ValueError(f"op {op} maps to {len(paths)} scope paths in "
                             f"modules {sorted(modules)}: {sorted(paths)}")
        if paths and rx.search(paths.pop()):
            seconds += s
    return seconds / run.trace.n_devices


def read(run):
    seconds = scoped_seconds(run, SCOPE)
    if seconds is None:
        return None
    return 1e6 * seconds / run.window.totals["frames"]
