"""One run of one cell: find the cell's files by the names in
``BENCHMARK.json``, check the chip, run the cell's driver, reduce the
trace, call each per-layer metric's reader, print the result line.

Everything a cell needs is data found by name:

- ``configs/<config>.json`` (the file ``BENCHMARK.json`` names) holds
  the sizes and names its ``driver``, a module ``chipbench/<driver>.py``
  with ``run(run: Run) -> dict``;
- ``mixes/<traffic>.json`` holds the traffic's parameters;
- ``metrics/<metric>.py`` holds ``read(run, out) -> float | None`` for a
  per-layer metric.

A later change adds a cell, a mix or a metric by adding files and
entries; it edits none of these.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from .spans import CompileClock, GcPauses, Spans

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH_DIR))


class NoChip(RuntimeError):
    """The machine cannot run the cell: no TPU, too few chips, or a chip
    without published peaks."""


def process_start_wall() -> float:
    """Wall-clock time this process started, from ``/proc``."""
    with open("/proc/self/stat") as fh:
        fields = fh.read().rsplit(")", 1)[1].split()
    start_ticks = int(fields[19])
    with open("/proc/stat") as fh:
        btime = next(int(line.split()[1]) for line in fh
                     if line.startswith("btime"))
    return btime + start_ticks / os.sysconf("SC_CLK_TCK")


# ------------------------------------------------------------ resolution
def load_benchmark(root: str = CHECKOUT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _bench_dir(root: str) -> str:
    return os.path.join(root, os.path.relpath(BENCH_DIR, CHECKOUT))


def mix_path(traffic: str, root: str = CHECKOUT) -> str:
    return os.path.join(_bench_dir(root), "mixes", f"{traffic}.json")


def metric_path(name: str, root: str = CHECKOUT) -> str:
    return os.path.join(_bench_dir(root), "metrics", f"{name}.py")


def driver_path(name: str, root: str = CHECKOUT) -> str:
    return os.path.join(_bench_dir(root), "chipbench", f"{name}.py")


def resolve(bench: Dict, workload: str, root: str = CHECKOUT) -> Dict:
    """The cell ``workload`` with its configuration, mix, driver module
    name, end-to-end metrics and per-layer metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, cfg_entry["file"])) as fh:
        config = json.load(fh)
    with open(mix_path(cell["traffic"], root)) as fh:
        mix = json.load(fh)
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return {"cell": cell, "config": config, "mix": mix,
            "driver": config["driver"], "end_to_end": e2e,
            "per_layer": layer}


def load_reader(name: str, root: str = CHECKOUT):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}",
        metric_path(name, root))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ run
@dataclass
class Run:
    workload: str
    config: Dict
    mix: Dict
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    spans: Spans = field(default_factory=Spans)
    clock: Optional[CompileClock] = None
    trace_dir: Optional[str] = None
    #: perf_counter reading at the window's start and end
    window: List[float] = field(default_factory=list)
    devices: List[Any] = field(default_factory=list)
    peaks: Dict = field(default_factory=dict)
    #: wall-clock time the process started (set-up is counted from it)
    t_process_wall: float = 0.0
    #: wall-clock time the window started
    window_wall: float = 0.0
    #: a fault planted under the timed path (the check's own tests
    #: only; the command line has no way to set it)
    fault: Optional[str] = None
    #: seconds of check work done before the window (readings taken
    #: for the comparison), which ``setup_s`` leaves out
    check_in_setup_s: float = 0.0
    #: the collector's pauses inside the window
    gc_pauses: GcPauses = field(default_factory=GcPauses)
    _profiling: bool = False
    _settled: bool = False

    @property
    def seed32(self) -> int:
        """The seed folded into the 31 bits the program's keys take."""
        return self.seed % (1 << 31)

    def settle(self) -> None:
        """The last of set-up, before any timed work or traffic starts:
        collect once and freeze what set-up left (the program's traced
        and compiled caches, the driver's inputs), so that the
        collections the window's own garbage sets off do not walk it.
        Otherwise a full collection over it (most of a second) falls
        inside the window or not, as set-up left the collector's
        counts."""
        if not self._settled:
            gc.collect()
            gc.freeze()
            self._settled = True

    def start_window(self) -> float:
        self.settle()
        self.gc_pauses.arm()
        if self.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._profiling = True
            self._window_span = self.spans.span("window")
            self._window_span.__enter__()
        self.window_wall = time.time()
        self.window = [time.perf_counter()]
        return self.window[0]

    @property
    def setup_s(self) -> float:
        """Process start to the start of the window, less the check's
        own readings taken before it."""
        return self.window_wall - self.t_process_wall - self.check_in_setup_s

    def end_window(self) -> float:
        self.window.append(time.perf_counter())
        if self._profiling:
            import jax

            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._profiling = False
        self.gc_pauses.disarm()
        gc.unfreeze()
        self._settled = False
        return self.window[1] - self.window[0]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip of the run (0 where the
        backend keeps no statistics, as the CPU's does not)."""
        stats = [d.memory_stats() for d in self.devices]
        return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def check_devices(chips: int):
    """The devices of this run; raises ``NoChip`` unless JAX finds at
    least ``chips`` TPUs with published peaks."""
    import jax

    from .peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    try:
        peaks = peaks_for(devices[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devices, peaks


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says.  Every
    program is cached, however quickly it compiled, so a run's second
    start finds all of them."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def driver_module(name: str):
    return importlib.import_module(f"chipbench.{name}")


def per_layer_values(spec: Dict, run: Run, out: Dict,
                     root: str = CHECKOUT) -> Dict[str, Dict]:
    metrics = {}
    for m in spec["per_layer"]:
        value = load_reader(m["name"], root)(run, out)
        if value is not None:
            if not math.isfinite(value):
                raise ValueError(f"per-layer metric {m['name']} read "
                                 f"{value}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def execute(workload: str, seed: int, seconds: float, trace: bool,
            trace_dir: Optional[str] = None, root: str = CHECKOUT,
            fault: Optional[str] = None) -> Dict:
    """Run one cell on the chip and return its result object.  ``fault``
    plants a fault under the timed path (the check's own tests only)."""
    t_proc = process_start_wall()
    bench = load_benchmark(root)
    spec = resolve(bench, workload, root)
    chips = spec["cell"]["chips"]
    devices, peaks = check_devices(chips)
    enable_compile_cache()
    sys.path.insert(0, os.path.join(root, "src"))
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tmp:
        run = Run(workload=workload, config=spec["config"], mix=spec["mix"],
                  seed=seed, seconds=seconds, trace=trace, chips=chips,
                  spans=Spans(enabled=trace), clock=CompileClock(),
                  trace_dir=trace_dir or tmp, devices=devices[:chips],
                  peaks=peaks, t_process_wall=t_proc, fault=fault)
        out = driver_module(spec["driver"]).run(run)
        reduced = None
        if trace:
            from . import trace as T

            path = T.newest_xplane(run.trace_dir)
            if path is None:
                raise RuntimeError("the profiler wrote no trace")
            tr = T.load(path)
            reduced = T.reduce(tr, T.chip_planes(tr, chips))
            out["reduced"] = reduced
            if trace_dir:
                with open(os.path.join(trace_dir, "layout.json"), "w") as fh:
                    json.dump({"planes": tr.layout,
                               "module_s": reduced.module_s,
                               "module_n": reduced.module_n}, fh, indent=1)
    e2e_vals = out["end_to_end"]
    metrics: Dict[str, Dict] = {}
    if trace:
        metrics = per_layer_values(spec, run, out, root)
    else:
        for m in spec["end_to_end"]:
            if m["name"] not in e2e_vals:
                raise KeyError(f"driver {spec['driver']} reported no "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": e2e_vals[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.top_ops],
            "idle_gaps": [[k, v] for k, v in reduced.idle_gaps]}
    result["readings"] = {**(out.get("readings") or {}),
                          "window_gc": run.gc_pauses.summary()}
    result["seconds"] = {"setup": run.setup_s, "window": run.window_s,
                         "check": out["check_s"] + run.check_in_setup_s}
    result["compared"] = out["compared"]
    return result
