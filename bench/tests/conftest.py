"""Shared set-up of the benchmark's tests: the benchmark's own modules and
the program's sources on the path, and cells cut to CPU size."""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
for _p in (SRC, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: Sizes at which a cell rehearses on the CPU: a global problem of 8x16x16
#: zones and 2x2 sets for kripke-tioga, and 8- and 16-rank points for
#: kripke-table3.
TINY = {
    "kripke-tioga": {"zones_per_rank": [4, 8, 8], "n_dirsets": 2, "n_groupsets": 2},
    "kripke-table3": {"points": [[2, 2, 2], [4, 2, 2]]},
}


@pytest.fixture(scope="session")
def manifest():
    import harness

    return harness.manifest()


@pytest.fixture
def tiny_cell(manifest):
    """``tiny_cell(workload)``: the cell with its configuration cut to
    :data:`TINY`."""
    import harness

    def make(workload):
        cell = harness.cell(manifest, workload)
        cfg = dict(cell.config, **TINY[cell.config["name"]])
        return dataclasses.replace(cell, config=cfg)

    return make


def run_python(code: str, *, n_devices: int = 1, timeout: int = 600) -> str:
    """Run ``code`` in a fresh CPU-only process with ``n_devices`` virtual
    devices and the benchmark on its path; return its standard output."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join([BENCH_DIR, SRC, os.path.dirname(__file__)])
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=ROOT,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout
