"""chip_smoke.py: refuses to run without a TPU, and its phases rehearse on
the CPU at tiny sizes (one device, and four virtual devices)."""

import importlib.util
import json
import os

from helpers import run_with_devices

from repro.benchpark.spec import PAPER_EXPERIMENTS
from repro.core import compat
from repro.core.backend import JaxBackend

SMOKE = os.path.join(os.path.dirname(__file__), "..", "chip_smoke.py")


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lines(out: str) -> list:
    return [json.loads(line) for line in out.splitlines() if line.startswith("{")]


def test_smoke_fails_without_a_tpu(capsys):
    assert _smoke().main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_smoke_phases_rehearse_on_cpu(capsys):
    cs = _smoke()
    dev = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    mesh = compat.make_mesh((1, 1, 1), ("x", "y", "z"))
    for case in cs.app_cases((1, 1, 1), small=True):
        cs.run_app(case, mesh, mesh, dev)
    spec = PAPER_EXPERIMENTS["kripke-weak-tioga"]
    cs.reduce_point(spec, 8, JaxBackend(interpret=True), dev)
    lines = _lines(capsys.readouterr().out)
    apps = [ln for ln in lines if ln["phase"] == "apps"]
    assert [ln["app"] for ln in apps] == ["laghos", "beatnik", "amg", "kripke"]
    assert all(ln["matches_cpu"] and len(ln["step_seconds"]) == 3 for ln in apps)
    (red,) = [ln for ln in lines if ln["phase"] == "reduce"]
    assert red["profile_json_identical"] and red["network_rows_identical"]
    assert red["measured_on"] == "cpu" and red["pallas_compiled"] is False
    assert set(red["pallas_block_reduce_seconds"]) == {"add", "maximum", "minimum"}


def test_smoke_four_chip_path_rehearses_on_four_devices():
    out = run_with_devices(
        f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location("cs", {SMOKE!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.core import compat
        dev = {{"platform": "cpu", "device_kind": "cpu", "device_count": 4}}
        mesh = compat.make_mesh((2, 2, 1), ("x", "y", "z"), devices=jax.devices())
        for case in cs.app_cases((2, 2, 1), small=True):
            cs.run_app_sharded(case, mesh, dev)
        """,
        n_devices=4,
    )
    lines = _lines(out)
    assert [ln["app"] for ln in lines] == ["laghos", "beatnik", "amg", "kripke"]
    for ln in lines:
        assert ln["matches_reference"] and set(ln["output_devices"]) == {4}
