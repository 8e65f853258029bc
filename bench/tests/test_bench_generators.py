"""The traffic generators rehearse on the CPU at tiny sizes: the rest of a run, past the
harness's look for a chip.  With the timed path broken underneath, each
fault a cell can have must turn ``correct`` false."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest
from conftest import ROOT, run_python


def _run(cell, *, seconds=0.2, seed=2**31 + 5):
    import harness
    import jax

    return harness.run(
        cell,
        seed=seed,
        seconds=seconds,
        trace=False,
        devices=jax.devices()[:1],
        t0=time.perf_counter(),
    )


def test_run_fails_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            "bench/run.py",
            "--workload",
            "kripke-tioga.exec1",
            "--seed",
            "1",
            "--seconds",
            "1",
            "--trace",
            "0",
        ],
        cwd=ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_exec_rehearses_on_one_cpu_device(tiny_cell):
    line = _run(tiny_cell("kripke-tioga.exec1"))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "step_s", "step_p95_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["max_rel_err"]["value"] < 1e-5
    assert line["checks"]["missing_region_scopes"]["value"] == 0


def test_turnaround_rehearses_on_one_cpu_device(tiny_cell):
    line = _run(tiny_cell("kripke-table3.turnaround"))
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] % 2 == 0  # whole sweeps of two points
    assert set(line["metrics"]) == {"setup_s", "points_per_s"}
    assert line["checks"]["profile_fields_differing"]["value"] == 0


def _break_sweep(monkeypatch, fault):
    from repro.apps import kripke

    real = kripke.distributed_sweep

    def broken(cfg, mesh):
        run = real(cfg, mesh)
        return lambda q: fault(run(q), q)

    monkeypatch.setattr(kripke, "distributed_sweep", broken)


@pytest.mark.parametrize(
    "fault",
    [
        pytest.param(lambda out, q: out * 0 + q, id="state_unchanged"),
        pytest.param(
            lambda out, q: out.at[0, 0, 0, 0, 0, 0, 0].multiply(1.01),
            id="answer_altered",
        ),
    ],
)
def test_exec_fault_turns_correct_false(tiny_cell, monkeypatch, fault):
    _break_sweep(monkeypatch, fault)
    line = _run(tiny_cell("kripke-tioga.exec1"))
    assert not line["correct"]
    c = line["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_exec_without_region_scopes_is_not_correct(tiny_cell, monkeypatch):
    import contextlib

    from repro.apps import kripke

    monkeypatch.setattr(kripke, "comm_region", lambda name: contextlib.nullcontext())
    line = _run(tiny_cell("kripke-tioga.exec1"))
    assert not line["correct"]
    assert line["checks"]["missing_region_scopes"]["value"] == 2
    assert line["checks"]["max_rel_err"]["value"] < 1e-5


def test_turnaround_answer_altered_is_not_correct(tiny_cell, monkeypatch):
    from repro.core.profiler import CommPatternProfiler

    real = CommPatternProfiler.from_recorder

    def altered(rec, **kw):
        prof = real(rec, **kw)
        prof.regions["sweep_comm"].total_sends += 1
        return prof

    monkeypatch.setattr(CommPatternProfiler, "from_recorder", staticmethod(altered))
    line = _run(tiny_cell("kripke-table3.turnaround"))
    assert not line["correct"]
    assert line["checks"]["profile_fields_differing"]["value"] >= 2


def test_turnaround_off_the_device_backend_fails_the_run(tiny_cell, monkeypatch):
    from repro.core import backend

    numpy_only = backend.NumpyBackend()
    monkeypatch.setattr(backend, "resolve_backend", lambda b=None: numpy_only)
    with pytest.raises(RuntimeError, match="off the device"):
        _run(tiny_cell("kripke-table3.turnaround"))


def test_device_reduction_argument_follows_run_experiment():
    import harness

    drv = harness.load_module(os.path.join(ROOT, "bench", "generators", "turnaround.py"))

    def with_backend(spec, *, backend=None):
        pass

    def without(spec, *, cache=None):
        pass

    assert drv.device_reduction(with_backend) == {"backend": "jax"}
    assert drv.device_reduction(without) == {}


# kripke-tioga on the exec4 mix: the (2, 2, 1) mesh over four devices.
_FOUR = """
import dataclasses, json, os, time, jax
import harness
from conftest import TINY
cell = harness.cell(harness.manifest(), "kripke-tioga.exec1")
cell = dataclasses.replace(
    cell,
    chips=4,
    config=dict(cell.config, **TINY["kripke-tioga"]),
    traffic=harness.load_json(os.path.join("bench", "traffic", "exec4.json")),
)
{fault}
line = harness.run(cell, seed=7, seconds=0.2, trace=False, devices=jax.devices(),
                   t0=time.perf_counter())
print(json.dumps(line))
"""


def test_exec_rehearses_on_four_virtual_devices():
    line = json.loads(run_python(_FOUR.format(fault=""), n_devices=4).splitlines()[-1])
    assert line["correct"] and line["device"]["count"] == 4
    assert line["checks"]["missing_region_scopes"]["value"] == 0


def test_exec4_without_the_exchange_is_not_correct():
    fault = (
        "from repro.core import collectives\n"
        "import jax.numpy as jnp\n"
        "collectives.ppermute = lambda x, *a, **k: jnp.zeros_like(x)\n"
    )
    out = run_python(_FOUR.format(fault=fault), n_devices=4)
    line = json.loads(out.splitlines()[-1])
    assert not line["correct"]
    c = line["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
