"""The plain references agree with the program where it is sound, and each
cell's control comes out not correct against its limit."""

from __future__ import annotations

import json

import ml_dtypes
import numpy as np
import pytest


def _table3(manifest):
    import harness

    return harness.cell(manifest, "kripke-table3.turnaround")


@pytest.mark.parametrize(
    "fuse, octants, decomp",
    [
        (True, 1, (16, 16, 8)),
        (True, 1, (3, 5, 2)),
        (False, 2, (2, 2, 2)),
        (False, 2, (4, 2, 1)),
        (True, 3, (1, 1, 3)),
        (False, 8, (3, 2, 2)),
    ],
)
def test_table3_reference_matches_the_program(manifest, fuse, octants, decomp):
    from repro.benchpark.runner import run_experiment

    cell = _table3(manifest)
    cfg = dict(
        cell.config, points=[list(decomp)], fuse_messages=fuse, n_octants=octants
    )
    (prof,) = run_experiment(
        cell.program().spec(cfg),
        verbose=False,
        cache=None,
        executor="serial",
        retries=0,
    )
    got = json.loads(prof.to_json())
    got = {"n_ranks": got["n_ranks"], "regions": got["regions"]}
    ref = cell.reference()
    assert ref.fields_differing(got, ref.profile(cfg, decomp)) == 0


def test_table3_control_fails_in_every_sweep_of_the_cell(manifest):
    """The int32 control overflows at the 512-rank point, which every
    sweep of the cell visits; the smaller points stay within int32."""
    cell = _table3(manifest)
    ref, cfg = cell.reference(), cell.config
    limit = cfg["limits"]["profile_fields_differing"]
    differing = {}
    for decomp in cfg["points"]:
        want = ref.profile(cfg, decomp)
        low = ref.profile(cfg, decomp, int_dtype=np.int32)
        differing[tuple(decomp)] = ref.fields_differing(low, want)
    assert sum(differing.values()) > limit
    assert differing[(8, 8, 8)] == 2
    assert differing[(4, 4, 4)] == 0


def test_tioga_control_fails_and_float32_passes(tiny_cell):
    cell = tiny_cell("kripke-tioga.exec1")
    ref, cfg = cell.reference(), cell.config
    shape = cell.program().global_shape(cfg)
    q = np.random.default_rng(3).uniform(0.5, 1.5, shape).astype(np.float32)
    limit = cfg["limits"]["max_rel_err"]
    assert ref.max_rel_err(cfg, q, [], dtype=ml_dtypes.bfloat16) > 3 * limit
    assert ref.max_rel_err(cfg, q, [], dtype=np.float32) < limit / 3
