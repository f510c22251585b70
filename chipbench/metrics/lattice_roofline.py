"""Roofline share of the transducer lattice kernel: per call, the larger
of its bytes over HBM bandwidth and its operations over peak
(``flops.lattice_flops_bytes`` at the cell's padded lattice shape), times
the calls, over the kernel's device time in the trace.

Silent where no lattice kernel ran: a change that takes the kernel off
the path leaves its roofline unread, and ``step_mfu.asr`` still bounds
the whole step."""
from chipbench import flops

#: the kernel's name in the trace (``kernels/rnnt_lattice``)
KERNEL = r"rnnt_lattice"


def read(run):
    seconds, calls = run.trace.ops_matching(KERNEL)
    if not calls:
        return None
    ops, nbytes = flops.lattice_flops_bytes(*run.facts["lattice_shape"])
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["flops_bf16"])
    return 100.0 * least * calls / run.trace.n_devices / seconds
