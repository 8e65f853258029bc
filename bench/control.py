"""Readings that set a cell's correctness limit: the program's over many
seeds, and the control's (the reference in the next precision below, or
with the configuration's guarantee broken), at the cell's own size.

    python bench/control.py --workload <name> --seeds <n> [<n> ...] [--control-seeds <k>]

Prints one JSON line per seed.  For an executed cell: the timed entry's
``max_rel_err`` on that seed's input, and on the first ``--control-seeds``
seeds the control's (the reference computed in bfloat16 in place of the
float32 program).  For a turnaround cell: the control's
``profile_fields_differing`` at each point (the reference summing its
totals in int32 in place of int64); the program's readings at these sizes
are the benchmark's own runs.  The benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def exec_readings(cell, seeds, devices, control_seeds: int):
    import jax
    import ml_dtypes
    import numpy as np

    from repro.core import compat

    prog, ref, cfg = cell.program(), cell.reference(), cell.config
    mesh_shape = tuple(cell.traffic["mesh"])
    n = math.prod(mesh_shape)
    mesh = compat.make_mesh(mesh_shape, ("x", "y", "z"), devices=devices[:n])
    sharding = prog.input_sharding(mesh)
    step = None
    for i, seed in enumerate(seeds):
        q = prog.make_input(cfg, seed, sharding)
        if step is None:
            step = jax.jit(prog.program(cfg, mesh)).lower(q).compile()
        out = np.asarray(step(q))
        q = np.asarray(q)
        line = {"seed": seed, "max_rel_err": ref.max_rel_err(cfg, q, [out])}
        if i < control_seeds:
            line["control_max_rel_err"] = ref.max_rel_err(
                cfg, q, [], dtype=ml_dtypes.bfloat16
            )
        yield line


def turnaround_readings(cell, seeds):
    import numpy as np

    ref, cfg = cell.reference(), cell.config
    for seed in seeds:
        for decomp in cfg["points"]:
            want = ref.profile(cfg, decomp)
            low = ref.profile(cfg, decomp, int_dtype=np.int32)
            yield {
                "seed": seed,
                "decomp": decomp,
                "control_profile_fields_differing": ref.fields_differing(low, want),
            }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument(
        "--control-seeds",
        type=int,
        default=3,
        help="executed cells: read the control on this many of the seeds",
    )
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness

    cell = harness.cell(harness.manifest(), args.workload)
    harness.use_compile_cache()
    devices = harness.chips(cell.chips)
    if devices is None:
        return 1
    if cell.traffic["generator"] == "exec":
        lines = exec_readings(cell, args.seeds, devices, args.control_seeds)
    else:
        lines = turnaround_readings(cell, args.seeds)
    for line in lines:
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
