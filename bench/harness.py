"""The benchmark's machinery: the manifest, cells, the measured window, host
spans, the trace reduction and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix or per-layer metric lives in
files of its own, found by name:

- ``configs/<config>.json``: the deployment as it is run, with its
  ``limits`` for the correctness comparison;
- ``configs/<config>.py``: the program side of that configuration (how to
  build its input and its entry); ``configs/<config>.reference.py``: its
  plain reference, which imports nothing of the program;
- ``traffic/<traffic>.json``: the mix's parameters, and the general
  generator (``generators/<generator>.py``) that reads them;
- ``metrics/<metric>.py``: a reader ``read(obs)`` of one per-layer metric,
  which returns ``None`` when it finds nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: Fixed, inside the checkout: the path is part of the cache's key.
COMPILE_CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold ``-``)."""
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _listed(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports
    per_layer: list

    def program(self) -> ModuleType:
        return _load("configs", f"{self.config['name']}.py")

    def reference(self) -> ModuleType:
        return _load("configs", f"{self.config['name']}.reference.py")

    def generator(self) -> ModuleType:
        return _load("generators", f"{self.traffic['generator']}.py")

    def reader(self, metric: str) -> ModuleType:
        return _load("metrics", f"{metric}.py")


def _load(*parts) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, *parts))


def cell(bench: dict, workload: str) -> Cell:
    """The cell ``workload`` of the manifest ``bench``."""
    (entry,) = [w for w in bench["workloads"] if w["name"] == workload] or [None]
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = os.path.join(BENCH_DIR, "configs", f"{entry['config']}.json")
    traffic = os.path.join(BENCH_DIR, "traffic", f"{entry['traffic']}.json")
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=load_json(config),
        traffic=load_json(traffic),
        end_to_end=[m for m in bench["end_to_end"] if _listed(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _listed(m, workload)],
    )


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at :data:`COMPILE_CACHE_DIR`, for
    every program however small, so that only a cell's first run in a
    checkout compiles.  Called before anything touches a device."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def chips(n: int):
    """The first ``n`` accelerator devices, or ``None`` (with the reason on
    standard error) when JAX finds no accelerator or fewer than ``n``."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        print("bench: JAX finds no accelerator, only the CPU", file=sys.stderr)
        return None
    if len(devices) < n:
        print(
            f"bench: the cell needs {n} chips, JAX finds {len(devices)}",
            file=sys.stderr,
        )
        return None
    return devices[:n]


class Spans:
    """Host spans around the calls into each layer: durations by name, and
    the same spans as profiler annotations (``bench.<name>``) so that a
    traced run can name the device's idle gaps."""

    def __init__(self):
        self.seconds: dict = {}

    @contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


class Window:
    """The measured window.  With a trace directory the profiler records it
    (started before the window opens, stopped after it closes); the window
    itself is the ``bench.window`` annotation.  The generator closes the
    window at a boundary of its own work by setting :attr:`end`."""

    def __init__(self, seconds: float, trace_dir: str | None = None):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.start = self.end = None

    def __enter__(self):
        import jax

        if self.trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._note = jax.profiler.TraceAnnotation("bench.window")
        self._note.__enter__()
        self.start = time.perf_counter()
        return self

    def over(self, now: float) -> bool:
        return now - self.start >= self.seconds

    def __exit__(self, *exc):
        import jax

        if self.end is None:
            self.end = time.perf_counter()
        self._note.__exit__(*exc)
        if self.trace_dir:
            jax.profiler.stop_trace()
        return False

    @property
    def length(self) -> float:
        return self.end - self.start


@dataclass
class Outcome:
    """What a generator hands back after its window and its check."""

    window: Window
    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value (``setup_s`` is the harness's)
    checks: dict  # compared name -> (value, limit); correct iff value <= limit
    obs: dict = field(default_factory=dict)  # what per-layer readers read


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where a backend keeps no
    count, as the CPU's does)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(
    cell: Cell, *, seed: int, seconds: float, trace: bool, devices, t0: float
) -> dict:
    """Run ``cell`` on ``devices`` and return its result line as a dict.
    ``t0`` is the process's start on the host clock (``setup_s`` counts
    from it)."""
    import peaks as peaks_table

    trace_dir = None
    if trace:
        trace_dir = os.path.join(TRACE_DIR, cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = cell.generator().run(
        cell, seed=seed, seconds=seconds, trace_dir=trace_dir, devices=devices
    )
    setup_s = out.window.start - t0
    kind = devices[0].device_kind
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": out.obs.pop("memory_peak_bytes"),
    }
    metrics, breakdown = {}, None
    if trace:
        import xplane

        summary = xplane.load(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        obs = dict(out.obs, trace=summary, peaks=peaks_table.peaks(kind))
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    correct = all(v <= lim for v, lim in out.checks.values())
    line = {
        "correct": correct,
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {
        k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()
    }
    return line


def emit(line: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
