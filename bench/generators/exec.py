"""Executed traffic: step the configuration's jitted shard_map entry back to
back, closed loop, on the mesh the traffic names.

Set-up builds the input on the device from the seed, compiles the entry
(or loads it from the compile cache) and runs one warm-up step.  The window
then runs steps, each ended by ``block_until_ready``, and closes at the
first step boundary after ``seconds``.  Afterwards the output of one step
drawn from the seed among the first few, and of the last step, are read
back and compared with the configuration's plain reference; the compiled
program must also still hold its ``commr::`` region scopes.
"""

from __future__ import annotations

import math
import re
import sys
import time

import numpy as np

from harness import Outcome, Window, memory_peak_bytes

#: The sampled step is drawn from the window's first steps.
SAMPLE_FROM = 8


def run(cell, *, seed: int, seconds: float, trace_dir, devices) -> Outcome:
    import jax

    from repro.core import compat

    prog, ref, cfg = cell.program(), cell.reference(), cell.config
    mesh_shape = tuple(cell.traffic["mesh"])
    n = math.prod(mesh_shape)
    if n != cell.chips:
        raise ValueError(f"mesh {mesh_shape} does not fill {cell.chips} chips")
    mesh = compat.make_mesh(mesh_shape, ("x", "y", "z"), devices=devices[:n])

    q = prog.make_input(cfg, seed, prog.input_sharding(mesh))
    step = jax.jit(prog.program(cfg, mesh)).lower(q).compile()
    scopes = set(re.findall(r"commr::(\w+)", step.as_text()))
    missing = prog.region_scopes(cfg, mesh_shape) - scopes
    temp_bytes = step.memory_analysis().temp_size_in_bytes
    out = jax.block_until_ready(step(q))  # warm-up

    sample_at = int(np.random.default_rng(seed % 2**64).integers(SAMPLE_FROM))
    sample = None
    durations = []
    with Window(seconds, trace_dir) as win:
        t = win.start
        while True:
            t0 = t
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = jax.block_until_ready(step(q))
            t = time.perf_counter()
            durations.append(t - t0)
            if len(durations) - 1 == sample_at:
                sample = out
            if win.over(t):
                win.end = t
                break
    steps = len(durations)
    peak = memory_peak_bytes(devices[:n])
    slowest = sorted(range(steps), key=lambda i: -durations[i])[:3]
    print(
        f"exec: {steps} steps in {win.length:.3f} s, slowest (step, s): "
        f"{[(i, round(durations[i], 4)) for i in slowest]}",
        file=sys.stderr,
    )

    outputs = [np.asarray(out)] + ([] if sample is None else [np.asarray(sample)])
    q_host = np.asarray(q)
    del q, out, sample, step
    err = ref.max_rel_err(cfg, q_host, outputs)
    limits = cfg["limits"]
    return Outcome(
        window=win,
        attempted=steps,
        failed=0,
        end_to_end={
            "step_s": win.length / steps,
            "step_p95_s": float(np.percentile(durations, 95)),
        },
        checks={
            "max_rel_err": (err, limits["max_rel_err"]),
            "missing_region_scopes": (len(missing), limits["missing_region_scopes"]),
        },
        obs={
            "memory_peak_bytes": peak,
            "steps": steps,
            "n_chips": n,
            "step_temp_bytes": temp_bytes,
            "compulsory_bytes_per_chip": prog.compulsory_bytes(cfg, n),
        },
    )
