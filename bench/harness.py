"""One run of one cell: set-up, the measured window, the metrics, the check.

Everything is found by name from ``BENCHMARK.json``:

* the cell's configuration in ``bench/configs/<config>.json``, and its plain
  reference in ``bench/reference/<config["reference"]>.py``;
* the traffic mix in ``bench/traffic/<traffic>.json``, whose ``driver``
  names the general driver in ``bench/drivers/<driver>.py`` that reads it;
* the limits of the check in ``bench/limits/<cell>.json``;
* each metric in ``bench/metrics/<metric>.py``: ``read(run)`` returns the
  value, or None where the run has nothing to read.  A per-layer metric may
  add ``warm(run)``, called in set-up of every run of its cells, so that the
  first run in a checkout puts what it compiles in the compile cache, and
  ``probe(run)``, called in a traced run after the window under a trace of
  its own.

A driver module has ``setup(run)``, ``window(run)``, ``release(run)`` and
``check(run)``; it keeps its state on ``run.state`` and its readings in
``run.record``, calls ``run.setup_done()`` where the measured window
begins and ``run.start_trace()`` / ``run.stop_trace()`` around what a
traced run traces.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    path = os.path.join(BENCH, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``kind`` ("end_to_end" | "per_layer") metrics a cell reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


class Run:
    """What one run knows: its inputs, its readings and its trace."""

    def __init__(self, cell: dict, config: dict, traffic: dict,
                 limits: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, platform: str, log=print):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = trace
        self.t_start = t_start
        self.platform = platform
        self.reference = load_module("reference", config["reference"] + ".py")
        self.state: dict = {}
        self.record: dict = {}
        self.setup_s: Optional[float] = None
        self.compiles_in_window = 0
        self._in_window = False
        self._trace_dir: Optional[str] = None
        self._trace_t0 = 0.0
        self.traces: dict = {}           # "window" | "probe" -> Trace
        self.window_s: Optional[float] = None   # traced window length
        self.log = log

    def phase(self, name: str) -> None:
        """Log how far into the run a phase of set-up ended."""
        self.log(f"  {name}: {time.perf_counter() - self.t_start:.3f} s")

    # -- called by drivers ---------------------------------------------------

    def setup_done(self) -> None:
        """The measured window begins now: set-up ends here."""
        self.setup_s = time.perf_counter() - self.t_start
        self._in_window = True

    def window_done(self) -> None:
        self._in_window = False

    def start_trace(self, name: str = "window") -> None:
        if not self.trace:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix=f"bench-{name}-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._trace_t0 = time.perf_counter()

    def stop_trace(self, name: str = "window") -> None:
        if not self.trace:
            return
        import jax
        from bench import trace as trace_lib
        t = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        if name == "window":
            self.window_s = t
        try:
            self.traces[name] = trace_lib.Trace.load(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    def numerics(self, name: Optional[str] = None):
        """The reference's numerics: what the configuration states on this
        platform, or ``name``."""
        if name is None:
            name = self.config["precision"]["reference"].get(
                self.platform, self.config["precision"]["reference"]["other"])
        return self.reference.Numerics.named(name)

    def compile_listener(self) -> Callable:
        def listen(event, secs, **_):
            if self._in_window and event.endswith("backend_compile_duration"):
                self.compiles_in_window += 1
        return listen


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, *, platform: str,
             config: Optional[dict] = None, traffic: Optional[dict] = None,
             limits: Optional[dict] = None, log=print,
             keep: Optional[dict] = None) -> dict:
    """Run one cell once and return the result object.  ``config``,
    ``traffic`` and ``limits`` replace the cell's own files (tests run a
    cell at a small size this way); ``keep["run"]`` receives the ``Run``."""
    import jax

    from repro.launch.cache import use_compile_cache

    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = config or load_json("configs", cell["config"] + ".json")
    traffic = traffic or load_json("traffic", cell["traffic"] + ".json")
    limits = limits or load_json("limits", cell_name + ".json")
    kind = "per_layer" if trace else "end_to_end"
    metrics = cell_metrics(bench, cell_name, kind)
    run = Run(cell, config, traffic, limits, seed, seconds, trace, t_start,
              platform, log)
    if keep is not None:
        keep["run"] = run

    cache = use_compile_cache()
    # every program of the cell goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    listen = run.compile_listener()
    jax.monitoring.register_event_duration_secs_listener(listen)
    log(f"cell {cell_name}: config {cell['config']} traffic "
        f"{cell['traffic']} seed {seed} seconds {seconds} trace {int(trace)} "
        f"compile_cache {cache}")

    driver = load_module("drivers", traffic["driver"] + ".py")
    probes = [load_module("metrics", m["name"] + ".py")
              for m in cell_metrics(bench, cell_name, "per_layer")]
    run.phase("imports")
    driver.setup(run)
    for mod in probes:
        if hasattr(mod, "warm"):
            mod.warm(run)
    run.phase("probes")
    driver.window(run)
    run.window_done()
    dev = jax.devices()[0]
    memory_peak = peak_bytes(dev)
    if trace and any(hasattr(mod, "probe") for mod in probes):
        run.start_trace("probe")
        for mod in probes:
            if hasattr(mod, "probe"):
                mod.probe(run)
        run.stop_trace("probe")
    driver.release(run)
    gc.collect()
    jax.monitoring.unregister_event_duration_listener(listen)
    log(f"setup_s {run.setup_s:.3f} compiles_in_window "
        f"{run.compiles_in_window}")

    values = {}
    for m in metrics:
        v = load_module("metrics", m["name"] + ".py").read(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = driver.check(run)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct),
              "attempted": int(run.record.get("attempted", 0)),
              "failed": int(run.record.get("failed", 0)),
              "metrics": values, "device": device}
    if trace:
        from bench import trace as trace_lib
        tr = run.traces["window"]
        device["busy_s"] = tr.busy_s()
        device["window_s"] = run.window_s
        result["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": tr.idle_by_label(trace_lib.HOST_LABELS, 10)}
    result["checks"] = checks
    return result
