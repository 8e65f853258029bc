"""BENCHMARK.json and the files it names: allowed names and units, every
cell's files present, every per-layer metric's end-to-end metric reported
in its cells, at most half the cells on four chips; the work count and the
peaks table."""

from __future__ import annotations

import math
import os
import re

import pytest
from conftest import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def _reports(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def test_names_and_units_use_the_allowed_characters(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    names += [w["name"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    names += [w[k] for w in manifest["workloads"] for k in ("config", "traffic")]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in _metrics(manifest))
    for kind in ("end_to_end", "per_layer", "workloads", "configs"):
        listed = [e["name"] for e in manifest[kind]]
        assert len(listed) == len(set(listed)), kind


def test_every_cell_finds_its_files(manifest):
    import harness

    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        cfg = configs[w["config"]]
        assert cfg["file"] == f"bench/configs/{w['config']}.json"
        cell = harness.cell(manifest, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.config["source"] == cfg["source"]
        assert sorted(cell.config["reduced"]) == sorted(cfg["reduced"])
        for path in (
            f"configs/{w['config']}.py",
            f"configs/{w['config']}.reference.py",
            f"generators/{cell.traffic['generator']}.py",
        ):
            assert os.path.isfile(os.path.join(BENCH_DIR, path)), path
        mesh = cell.traffic.get("mesh")
        if mesh is not None:
            assert math.prod(mesh) == w["chips"]
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH_DIR, "metrics", f"{m['name']}.py"))


def test_every_per_layer_metric_moves_a_metric_its_cells_report(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert _reports(e2e[m["moves"]], w), (m["name"], w)


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"] if _reports(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(_reports(m, w["name"]) for m in manifest["per_layer"])


def test_at_most_half_the_cells_take_four_chips(manifest):
    chips = [w["chips"] for w in manifest["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


def test_the_command_stays_inside_the_benchmark(manifest):
    assert manifest["paths"] == ["bench"]
    script = manifest["command"][1]
    assert script.startswith("bench/") and os.path.isfile(os.path.join(ROOT, script))
    assert 1 <= manifest["run_seconds"] <= 51
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize(
    "mesh, per_chip", [((1, 1, 1), 603_979_776), ((2, 2, 1), 150_994_944)]
)
def test_kripke_tioga_compulsory_bytes(manifest, mesh, per_chip):
    import harness

    cell = harness.cell(manifest, "kripke-tioga.exec1")
    assert cell.program().compulsory_bytes(cell.config, math.prod(mesh)) == per_chip


def test_peaks_name_their_source_and_refuse_an_unknown_chip():
    import peaks

    v5e = peaks.peaks("TPU v5 lite")
    assert v5e.hbm_bytes_per_s == 819e9 and v5e.flops_bf16 == 197e12
    assert "TPU v5e" in peaks.SOURCE
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")
