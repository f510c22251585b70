"""The harness: finds a cell by name, sets it up, measures one window,
checks what the window produced against the reference, and prints the
result line.

A cell is ``config`` + ``traffic`` + ``chips`` in ``BENCHMARK.json``.
The configuration is the JSON file the entry names; the traffic mix is
``chipbench/traffic/<traffic>.json``, whose ``driver`` names the module
of ``chipbench/drivers`` that runs it; each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  So a cell, a mix or a metric is
added as files, and this module stays as it is.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoAccelerator(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's object
    traffic: dict           # the traffic file's object
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "chipbench", "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


class CompileClock:
    """Counts JAX's backend compiles (and their seconds) while alive."""

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set,
    else at the fixed ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def devices_for(chips: int, require_accelerator: bool = True):
    import jax
    devs = jax.devices()
    if require_accelerator and devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Window:
    seconds: float
    units: int                      # epochs, rounds: whole units of work
    failed: int
    totals: Dict[str, float]        # work summed over the window
    compiles: int


def measure(driver, seconds: float, clock: CompileClock,
            annotate: Callable) -> Window:
    """Whole units of work back to back until ``seconds`` have passed."""
    before = clock.count
    totals: Dict[str, float] = {}
    units = failed = 0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        while True:
            done = driver.step(annotate)
            units += 1
            failed += int(not done.pop("ok", True))
            for k, v in done.items():
                totals[k] = totals.get(k, 0.0) + v
            if time.perf_counter() - t0 >= seconds:
                break
    return Window(time.perf_counter() - t0, units, failed, totals,
                  clock.count - before)


def _load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class RunFacts:
    """What a per-layer reader may read."""
    cell: Cell
    window: Window
    facts: dict                     # the driver's shapes and counts
    trace: Optional[object]         # trace.Summary of the traced window
    peaks: dict
    chips: int


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, require_accelerator: bool = True,
        keep_trace: Optional[str] = None, compile_cache: bool = True,
        log=None) -> dict:
    """One run of one cell; returns the result object.  ``keep_trace``
    keeps a copy of a traced run's ``.xplane.pb`` at that path."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = load_cell(workload, root)
    import jax
    from jax.profiler import TraceAnnotation

    devs = devices_for(cell.chips, require_accelerator)
    cache = enable_compile_cache(root) if compile_cache else "off"
    clock = CompileClock()
    from chipbench.peaks import peaks as peaks_of
    peaks = peaks_of(devs[0].device_kind) if require_accelerator else None
    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache {cache}")
    drv_mod = importlib.import_module(
        "chipbench.drivers." + cell.traffic["driver"])
    driver = drv_mod.Driver(cell, seed)
    driver.setup(TraceAnnotation)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s, {clock.count} compiles "
        f"({clock.seconds:.3f} s)")

    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
    window = measure(driver, seconds, clock, TraceAnnotation)
    summary = None
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        from chipbench import trace as trace_mod
        xplane = trace_mod.find_xplane(tdir)
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        t1 = time.perf_counter()
        summary = trace_mod.reduce(xplane, n_devices=len(devs))
        log(f"trace: {os.path.getsize(xplane)} B written in "
            f"{t1 - t0:.1f} s, reduced in {time.perf_counter() - t1:.1f} s")
        shutil.rmtree(tdir, ignore_errors=True)
    device = device_info(devs)
    log(f"window {window.seconds:.3f} s, {window.units} units, "
        f"{window.compiles} compiles; peak HBM "
        f"{device['memory_peak_bytes']} B")

    facts = driver.facts()
    driver.release()
    gc.collect()
    checks = [Check("compiles_in_window", window.compiles, 0),
              Check("failed_units", window.failed, 0)]
    checks += driver.check()

    result = {"correct": all(c.ok for c in checks),
              "attempted": window.units, "failed": window.failed}
    if trace:
        rf = RunFacts(cell, window, facts, summary, peaks, len(devs))
        metrics = {}
        for m in cell.per_layer:
            value = _load_reader(m["name"])(rf)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["device"] = device
        result["breakdown"] = summary.breakdown()
    else:
        e2e = driver.end_to_end(window)
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    return result
