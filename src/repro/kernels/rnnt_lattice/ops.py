"""jit'd wrapper: Pallas on TPU / interpret for validation, XLA elsewhere.

Consumed by ``core/rnnt_loss.py:_lattice`` (the fused loss's pluggable
lattice backend); same dispatch convention as ``grad_sketch``/
``omp_gram``."""
from __future__ import annotations

from repro.kernels.backend import on_tpu
from repro.kernels.rnnt_lattice.kernel import rnnt_lattice as _pallas_lattice
from repro.kernels.rnnt_lattice.ref import rnnt_lattice_ref


def rnnt_lattice_op(mult, add, emit, *, use_pallas: bool = None,
                    interpret: bool = None):
    """(T, B, U1) x3 -> lattice rows (T, B, U1) fp32."""
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        interpret = (not on_tpu()) if interpret is None else interpret
        return _pallas_lattice(mult, add, emit, interpret=interpret)
    return rnnt_lattice_ref(mult, add, emit)
