"""Device idle share of the training window: 1 - busy / window, from the
device planes of the trace (``chipbench/trace.py``)."""


def read(run):
    return run.trace.idle_share_pct()
