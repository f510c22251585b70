"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per operation that ran, their ``XLA Modules`` line one event per
program run.  The host plane ``/host:CPU`` holds the benchmark's own
annotations (``bench.*``), on the same clock.  The window is the host's
``bench.window`` span.

- busy: the union of the device's op intervals inside the window,
  averaged over the devices;
- op and module times: device durations summed by name, inside the
  window.  An op is named by its HLO instruction name (the text before
  " = "; a Pallas kernel's is its kernel's name, such as
  ``%rnnt_lattice.1``), a program by its module name.  Ops nest (a
  while loop holds its body's ops), so op times overlap;
- idle gaps: the stretches of the window in which the device ran no op,
  each named by the innermost ``bench.*`` annotation that was open on the
  host at its middle; stretches under a microsecond, between one op and
  the next, are summed as ``between ops``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: ops that only hold other ops; left out of the breakdown's top list
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]*$")
#: shorter idle stretches are the gaps between one op and the next
SHORT_GAP_NS = 1000.0


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return found[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: Dict[str, float]          # summed over devices
    op_count: Dict[str, int]
    module_s: Dict[str, float]
    module_count: Dict[str, int]
    gaps: List[Tuple[str, float]]   # (host activity, seconds), device 0

    def idle_share_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def ops_matching(self, pattern: str) -> Tuple[float, int]:
        """Device seconds (per device) and count of ops whose name
        matches ``pattern``."""
        rx = re.compile(pattern)
        names = [n for n in self.op_s if rx.search(n)]
        return (sum(self.op_s[n] for n in names) / self.n_devices,
                sum(self.op_count[n] for n in names))

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        names = [n for n in self.module_s if rx.search(n)]
        return (sum(self.module_s[n] for n in names) / self.n_devices,
                sum(self.module_count[n] for n in names))

    def breakdown(self) -> dict:
        leaves = [kv for kv in self.op_s.items()
                  if not CONTAINER.match(kv[0])]
        top = sorted(leaves, key=lambda kv: -kv[1])[:10]
        by_host: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            by_host[name] += s
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s / self.n_devices] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(path: str, n_devices: int = 1,
           window: Optional[Tuple[float, float]] = None) -> Summary:
    """Reduce the trace at ``path``; the window is the host's
    ``bench.window`` span unless ``window`` gives (start, end) in the
    trace's nanoseconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_spans: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if window is None and len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} spans in the trace")
    lo, hi = window or windows[0]
    if len(devices) < n_devices:
        raise ValueError(f"{len(devices)} device planes in the trace, "
                         f"expected {n_devices}")
    devices = sorted(devices, key=lambda p: p.name)[:n_devices]

    op_s: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    module_s: Dict[str, float] = defaultdict(float)
    module_count: Dict[str, int] = defaultdict(int)
    busy, gaps = 0.0, []
    for i, plane in enumerate(devices):
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            ops = line.name == "XLA Ops"
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if e <= s:
                    continue
                if ops:
                    name = ev.name.split(" = ", 1)[0]
                    op_s[name] += (e - s) * 1e-9
                    op_count[name] += 1
                    intervals.append((s, e))
                else:
                    module_s[ev.name] += (e - s) * 1e-9
                    module_count[ev.name] += 1
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            short = 0.0
            for s, e in zip(edges[::2], edges[1::2]):
                if e - s >= SHORT_GAP_NS:
                    gaps.append((_host_activity(host_spans, (s + e) / 2),
                                 (e - s) * 1e-9))
                elif e > s:
                    short += (e - s) * 1e-9
            gaps.append(("between ops", short))
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy / n_devices,
                   n_devices=n_devices, op_s=dict(op_s),
                   op_count=dict(op_count), module_s=dict(module_s),
                   module_count=dict(module_count), gaps=gaps)


def _host_activity(spans, t) -> str:
    """The innermost benchmark annotation open at host time ``t``."""
    best = None
    for s, e, name in spans:
        if name != WINDOW and s <= t <= e and (
                best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "untagged"
