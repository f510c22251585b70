"""The trace reduction on a small trace recorded on a TPU v5e by
``chipbench/record_trace.py``: a 1024x1024 matmul, the transducer
lattice kernel on (64, 8, 128) rows and the batched Gram kernel on
(2, 256, 512), each dispatch and fetch inside the benchmark's own
annotations, with a 20 ms host sleep (``bench.plan``) between the first
fetch and the second dispatch.  The trace predates ``bench.window``, so
the window is given: from the first ``bench.dispatch`` to the last
``bench.fetch``.  The device clock runs about 1.2 ms ahead of the host's
in this trace, so the matmul falls before the window and the two kernels
inside ``bench.plan``.

Every expected number was read by hand off the file's events, in
nanoseconds."""
import os

import pytest

from chipbench import trace

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tpu_small.xplane.pb")
WINDOW_NS = (43199836.0, 65245144.0)
LATTICE_NS = (63285440.0, 63332543.0)      # %rnnt_lattice.1
GRAM_NS = (63504795.0, 63509620.0)         # %omp_gram_batched.1


def _ns(span):
    return span[1] - span[0]


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(TRACE, window=WINDOW_NS)


def test_window_busy_and_idle(summary):
    busy = (_ns(LATTICE_NS) + _ns(GRAM_NS)) * 1e-9
    assert summary.window_s == pytest.approx(_ns(WINDOW_NS) * 1e-9,
                                             rel=1e-12)
    assert summary.busy_s == pytest.approx(busy, rel=1e-12)
    assert summary.idle_share_pct() == pytest.approx(
        100.0 * (1.0 - busy / (_ns(WINDOW_NS) * 1e-9)), rel=1e-12)


def test_kernels_and_modules_by_name(summary):
    seconds, calls = summary.ops_matching(r"rnnt_lattice")
    assert calls == 1 and seconds == pytest.approx(_ns(LATTICE_NS) * 1e-9)
    seconds, calls = summary.ops_matching(r"omp_gram")
    assert calls == 1 and seconds == pytest.approx(_ns(GRAM_NS) * 1e-9)
    seconds, calls = summary.modules_matching(r"^jit_rnnt_lattice_op\(")
    assert calls == 1 and seconds == pytest.approx(47109e-9)
    # the matmul ran before the window
    assert summary.ops_matching(r"^%fusion$") == (0.0, 0)


def test_idle_gaps_named_by_host_activity(summary):
    assert summary.gaps == [
        ("bench.plan", pytest.approx((LATTICE_NS[0] - WINDOW_NS[0]) * 1e-9)),
        ("bench.plan", pytest.approx((GRAM_NS[0] - LATTICE_NS[1]) * 1e-9)),
        ("bench.plan", pytest.approx((WINDOW_NS[1] - GRAM_NS[1]) * 1e-9)),
        ("between ops", 0.0)]
    gaps = dict(summary.breakdown()["idle_gaps"])
    assert gaps["bench.plan"] == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-12)


def test_breakdown_lists_ops_by_time(summary):
    assert summary.breakdown()["device_ops"] == [
        ["%rnnt_lattice.1", pytest.approx(_ns(LATTICE_NS) * 1e-9)],
        ["%omp_gram_batched.1", pytest.approx(_ns(GRAM_NS) * 1e-9)]]


def test_trace_needs_a_window(tmp_path):
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(TRACE)
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
