"""The program's instrumentation: scopes, scope maps, spans, dispatch
records and counters, each read by a named consumer (PERF.md §3).

* Scopes: ``jax.named_scope`` at the layer boundaries — ``cnn``,
  ``encoder_lstm``, ``dnn``, ``pred_gru`` and ``joint_proj``
  (``models/rnnt.py``), ``rnnt_loss.fwd`` and ``rnnt_loss.bwd``
  (``core/rnnt_loss.py``), ``batch_gather``, ``grad_clip`` and
  ``optimizer`` (``train/engine.py``).  They only name the HLO's
  ``op_name`` metadata, so they cost nothing at run time.
* Scope maps: ``register_module`` keeps, for each compiled epoch
  executable, its post-optimisation HLO instruction names (the names a
  device trace gives its ops) with their ``op_name`` scope paths.
* Spans: ``span`` is a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>`` with its counts as arguments, on the profiler's clock
  with the device planes; a microsecond or two when no profiler runs.
* Dispatch records and counters: one ``Dispatch`` per epoch dispatch
  (the last ``MAX_DISPATCHES``), and named numbers and labels
  (``select.*``; ``epoch.gated_steps``, the padding rows the epoch
  scans skipped; ``compile.count`` and ``compile.seconds``, JAX's
  backend compiles since import).

The state is process-wide on purpose: a reader finds it after the
engine that produced it is gone, and without a handle on the engine.
The program records paths and counts only; what a path means, and the
arithmetic over it, is the reader's.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import sys
import threading
from typing import Dict, List, Optional, Union

import jax

#: dispatch records kept (the oldest go first)
MAX_DISPATCHES = 4096
#: scope maps kept, one per compiled executable (the oldest go first)
MAX_MODULES = 64

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_MODULE_RE = re.compile(r"^HloModule ([^\s,]+)", re.M)
# one HLO instruction with its op_name: "%name = <shape> op(...), ...,
# metadata={op_name="jit(run)/while/body/..." ...}"
_INSTR_RE = re.compile(
    r'^\s*(?:ROOT )?(%[^\s=]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
    re.M)

_lock = threading.Lock()
_values: Dict[str, Union[int, float, str]] = {}
_maps: "collections.OrderedDict[str, Dict[str, str]]" = (
    collections.OrderedDict())
_dispatches: "collections.deque[Dispatch]" = collections.deque(
    maxlen=MAX_DISPATCHES)
_n_registered = 0


@dataclasses.dataclass(frozen=True)
class Dispatch:
    """One epoch dispatch.  Positions are the per-example time axis:
    frames for RNN-T, tokens for LMs (``data/pipeline.py:unit_durations``).
    The counts are None where the plan carried none (a plan not built by
    the engine)."""
    module: Optional[str]           # the scope map's key
    compiled: bool                  # the dispatch added an executable
    steps: Optional[int]
    live_steps: Optional[int]
    positions: Optional[int]        # steps x batch x padded length
    live_positions: Optional[int]   # real lengths of the live units


def span(name: str, **counts) -> jax.profiler.TraceAnnotation:
    """``with span("epoch.dispatch", steps=24) as s: ...``: a profiler
    span named ``repro.<name>``; ``s.set_metadata(k=v)`` adds arguments
    known only at its end."""
    return jax.profiler.TraceAnnotation("repro." + name, **counts)


# -- scope maps ---------------------------------------------------------------
def parse_scopes(hlo_text: str) -> Dict[str, str]:
    """``{"%fusion.12": "jit(run)/while/body/.../encoder_lstm/...", ...}``
    for every instruction of a compiled module's text that has an
    ``op_name``."""
    return {name: sys.intern(path)
            for name, path in _INSTR_RE.findall(hlo_text)}


def register_module(hlo_text: str) -> str:
    """Keep the scope map of one compiled executable (its
    ``compiled.as_text()``); returns the key it is kept under, the HLO
    module's name with a process-wide serial number."""
    global _n_registered
    m = _MODULE_RE.search(hlo_text)
    scopes = parse_scopes(hlo_text)
    with _lock:
        key = f"{m.group(1) if m else 'module'}#{_n_registered}"
        _n_registered += 1
        _maps[key] = scopes
        while len(_maps) > MAX_MODULES:
            _maps.popitem(last=False)
    return key


def scope_maps() -> Dict[str, Dict[str, str]]:
    """The kept scope maps, by key."""
    with _lock:
        return dict(_maps)


# -- dispatch records ------------------------------------------------------------
def record_dispatch(d: Dispatch) -> None:
    _dispatches.append(d)


def dispatches() -> List[Dispatch]:
    """The kept dispatch records, oldest first."""
    return list(_dispatches)


# -- counters ----------------------------------------------------------------
def count(name: str, n: Union[int, float] = 1) -> None:
    with _lock:
        _values[name] = _values.get(name, 0) + n


def note(name: str, value: Union[int, float, str]) -> None:
    with _lock:
        _values[name] = value


def value(name: str, default=0):
    with _lock:
        return _values.get(name, default)


def _on_event_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        count("compile.count")
        count("compile.seconds", duration)


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)
