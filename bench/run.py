"""Run one benchmark cell on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` (see
``bench/harness.py`` for how its files are found).  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, the numbers compared for ``correct`` beside their limits.
Without an accelerator, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness

    cell = harness.cell(harness.manifest(), args.workload)
    harness.use_compile_cache()
    devices = harness.chips(cell.chips)
    if devices is None:
        return 1
    line = harness.run(
        cell,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        devices=devices,
        t0=T0,
    )
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
