"""Turnaround traffic: sweep the configuration's experiment from spec to
``thicket.Frame``, on the device reduction.

Each point runs through ``run_experiment(..., cache=None,
executor="serial")``; the reduction runs in the public ``trace_observer``
hook, which checks that it resolved to the device backend.  A sweep visits
every point once, in an order drawn from the seed, and ends with
``Frame.from_profiles`` over its profiles.  Set-up runs one such sweep, so
that every reduction shape is compiled; the window runs whole sweeps and
closes at the first sweep boundary after ``seconds``.  Afterwards every
profile of the window is compared with the configuration's plain
reference.
"""

from __future__ import annotations

import inspect
import json
import time
from dataclasses import replace

import numpy as np

from harness import Outcome, Spans, Window, memory_peak_bytes


def device_reduction(run_experiment) -> dict:
    """The keyword arguments that put ``run_experiment``'s reduction on the
    device: ``backend="jax"`` while it takes that argument, nothing once
    the device is its only path."""
    if "backend" in inspect.signature(run_experiment).parameters:
        return {"backend": "jax"}
    return {}


def run(cell, *, seed: int, seconds: float, trace_dir, devices) -> Outcome:
    from repro.benchpark.runner import run_experiment
    from repro.core.backend import JaxBackend, resolve_backend
    from repro.core.profiler import CommPatternProfiler, trace_observer
    from repro.core.thicket import Frame

    prog, ref, cfg = cell.program(), cell.reference(), cell.config
    spec = prog.spec(cfg)
    singles = [replace(spec, points=(p,)) for p in spec.points]
    kwargs = device_reduction(run_experiment)
    platform = devices[0].platform
    spans = Spans()
    off_device = []

    def observe(rec, *, name, replication, meta):
        be = resolve_backend()
        if not (isinstance(be, JaxBackend) and be.platform == platform):
            off_device.append(f"{name}: {be.name} on {getattr(be, 'platform', '?')}")
        with spans("reduce"):
            return CommPatternProfiler.from_recorder(
                rec, name=name, replication=replication, meta=meta
            )

    rng = np.random.default_rng(seed % 2**64)

    def sweep():
        profiles = []
        for i in rng.permutation(len(singles)):
            with spans("trace"), trace_observer(observe):
                (prof,) = run_experiment(
                    singles[i],
                    verbose=False,
                    cache=None,
                    executor="serial",
                    retries=0,
                    **kwargs,
                )
            profiles.append((singles[i].points[0].decomp, prof))
        with spans("frame"):
            Frame.from_profiles([p for _, p in profiles])
        if off_device:
            raise RuntimeError(f"the reduction ran off the device: {off_device}")
        return profiles

    sweep()  # warm-up: compiles every reduction shape of the sweep
    spans.seconds.clear()
    done = []
    with Window(seconds, trace_dir) as win:
        while True:
            done += sweep()
            t = time.perf_counter()
            if win.over(t):
                win.end = t
                break
    peak = memory_peak_bytes(devices)

    wanted = {}
    differing = 0
    for decomp, prof in done:
        if decomp not in wanted:
            wanted[decomp] = ref.profile(cfg, decomp)
        got = json.loads(prof.to_json())
        got = {"n_ranks": got["n_ranks"], "regions": got["regions"]}
        differing += ref.fields_differing(got, wanted[decomp])
    n = len(done)
    failed = sum(bool(p.meta.get("degraded")) for _, p in done)
    limits = cfg["limits"]
    return Outcome(
        window=win,
        attempted=n,
        failed=failed,
        end_to_end={"points_per_s": n / win.length},
        checks={
            "profile_fields_differing": (
                differing,
                limits["profile_fields_differing"],
            ),
        },
        obs={
            "memory_peak_bytes": peak,
            "points": n,
            "span_s": {k: sum(v) for k, v in spans.seconds.items()},
        },
    )
