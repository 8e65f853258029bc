"""amg-table3 on the CPU at small sizes: both cells rehearse, the plain
reference agrees with the program, the work count holds by hand, and each
fault either cell can have turns ``correct`` false."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time

import ml_dtypes
import numpy as np
import pytest

#: A 32x32x16 global problem (one distributed level, then the 16x16x8
#: coarse grid) for the exec cell, 8- and 16-rank points for the turnaround.
TINY = {"zones_per_rank": [4, 4, 2], "n_cycles": 12}
TINY_POINTS = {"points": [[2, 2, 2], [4, 2, 2]]}


@pytest.fixture
def cell(manifest):
    import harness

    def make(workload, **cut):
        c = harness.cell(manifest, workload)
        small = TINY if workload.endswith("exec1") else TINY_POINTS
        return dataclasses.replace(c, config={**c.config, **small, **cut})

    return make


def _run(cell, *, seconds=0.2, seed=2**31 + 11):
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


def test_exec_rehearses_on_one_cpu_device(cell):
    line = _run(cell("amg-table3.exec1"))
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "step_s", "step_p95_s"}
    assert line["checks"]["max_rel_err"]["value"] < 1e-5
    assert line["checks"]["missing_region_scopes"]["value"] == 0


def test_turnaround_rehearses_on_one_cpu_device(cell):
    line = _run(cell("amg-table3.turnaround"))
    assert line["correct"] and line["failed"] == 0
    assert line["attempted"] % 2 == 0  # whole sweeps of two points
    assert set(line["metrics"]) == {"setup_s", "points_per_s"}
    assert line["checks"]["profile_fields_differing"]["value"] == 0


def test_program_solve_matches_the_reference_on_seeded_rhs(cell):
    import jax

    from repro.core import compat

    c = cell("amg-table3.exec1")
    prog, ref, cfg = c.program(), c.reference(), c.config
    mesh = compat.make_mesh((1, 1, 1), ("x", "y", "z"), devices=jax.devices()[:1])
    for seed in (0, 2**33 + 1):
        f = prog.make_input(cfg, seed, prog.input_sharding(mesh))
        out = np.asarray(jax.jit(prog.program(cfg, mesh))(f))
        u, relres = ref.unpack(cfg, out.astype(np.float64))
        u_ref, relres_ref = ref.solve(cfg, np.asarray(f))
        np.testing.assert_allclose(u, u_ref, rtol=0, atol=1e-6 * np.abs(u_ref).max())
        assert relres_ref < 1e-5 and abs(relres - relres_ref) < 1e-5


@pytest.mark.parametrize("cycles", [1, 2])
@pytest.mark.parametrize("decomp", [(2, 2, 2), (4, 2, 2), (4, 3, 2)])
def test_reference_profile_matches_the_trace(cell, decomp, cycles):
    """(4, 2, 2) and (4, 3, 2) hold edge and interior ranks."""
    from repro.benchpark.runner import run_experiment

    c = cell("amg-table3.turnaround", traced_cycles=cycles, points=[list(decomp)])
    (prof,) = run_experiment(
        c.program().spec(c.config),
        verbose=False,
        cache=None,
        executor="serial",
        retries=0,
    )
    got = json.loads(prof.to_json())
    got = {"n_ranks": got["n_ranks"], "regions": got["regions"]}
    ref = c.reference()
    assert ref.fields_differing(got, ref.profile(c.config, decomp)) == 0


def test_reference_profile_counts_by_hand(manifest):
    """(2, 2, 2), one iteration: 64x64x32 global, two distributed levels;
    each rank has one neighbour per dim; level-0 faces 2048, 2048, 4096 B."""
    import harness

    c = harness.cell(manifest, "amg-table3.turnaround")
    p = c.reference().profile(c.config, (2, 2, 2))["regions"]
    assert p["mg_level_0"]["sends"] == [12, 12]  # 4 sweeps x 3 faces
    assert p["mg_level_0"]["bytes_sent"] == [4 * 8192] * 2
    assert p["mg_level_1"]["bytes_sent"] == [4 * 2048] * 2
    # residuals on levels 0 and 1, the Krylov matvec on level 0
    assert p["MatVecComm"]["instances"] == 3
    assert p["MatVecComm"]["bytes_sent"] == [2 * 8192 + 2048] * 2
    assert p["coarse_solve"]["coll_bytes"] == [8 * 8 * 4 * 4 * 7] * 2
    assert p["reduce_norm"]["kinds"] == {"psum": 4}  # (f,f), (r,z), (p,q), (r,r)
    assert p["reduce_norm"]["coll_bytes"] == [4 * 7] * 2  # 4 B x 2(8-1)/8, whole


def test_compulsory_bytes_by_hand(cell):
    """32x32x16, one distributed level of n = 16384 points over a 16x16x8
    coarse grid of m = 2048: a V-cycle is 4 sweeps and the residual at 3n,
    restriction n + n/8, prolongation 2n + n/8, and 8 coarse sweeps at 3m."""
    c = cell("amg-table3.exec1", n_cycles=2)
    n, m = 16384, 2048
    v_cycle = 5 * 3 * n + (n + n // 8) + (2 * n + n // 8) + 8 * 3 * m
    # (f,f) + V + (r,z); 2 x (matvec, (p,q), u, r); V + (r,z) + p; (r,r)
    values = n + v_cycle + 2 * n + 2 * 10 * n + v_cycle + 5 * n + n
    assert c.program().compulsory_bytes(c.config, 1) == 4 * values
    assert c.program().compulsory_bytes(c.config, 4) == values


def test_exec_control_fails_and_float32_passes(cell):
    c = cell("amg-table3.exec1")
    ref, cfg = c.reference(), c.config
    shape = c.program().global_shape(cfg)
    q = np.random.default_rng(3).uniform(0.5, 1.5, shape).astype(np.float32)
    limit = cfg["limits"]["max_rel_err"]
    assert ref.max_rel_err(cfg, q, [], dtype=ml_dtypes.bfloat16) > 100 * limit
    assert ref.max_rel_err(cfg, q, [], dtype=np.float32) < limit / 10


def test_turnaround_int32_control_stays_exact_at_the_cells_points(manifest):
    """No total of one traced iteration reaches 2^31 at 512 ranks (the
    largest, mg_level_0's bytes, is 73 times below it), so the int32
    control differs nowhere on these points."""
    import harness

    c = harness.cell(manifest, "amg-table3.turnaround")
    ref, cfg = c.reference(), c.config
    for decomp in cfg["points"]:
        want = ref.profile(cfg, decomp)
        assert ref.fields_differing(ref.profile(cfg, decomp, np.int32), want) == 0
    biggest = max(r["total_bytes_sent"] for r in want["regions"].values())
    assert biggest == 29_360_128


# --- planted faults ---------------------------------------------------------


def _break_solve(monkeypatch, fault):
    from repro.apps import amg

    real = amg.solve

    def broken(cfg, mesh):
        return fault(real, cfg, mesh)

    monkeypatch.setattr(amg, "solve", broken)


def _returns_input(real, cfg, mesh):
    import jax.numpy as jnp

    return lambda f: (f, jnp.float32(1.0))


def _in_bfloat16(real, cfg, mesh):
    import jax.numpy as jnp

    run = real(dataclasses.replace(cfg, dtype="bfloat16"), mesh)

    def low(f):
        u, relres = run(f.astype(jnp.bfloat16))
        return u.astype(f.dtype), relres.astype(f.dtype)

    return low


def _one_cycle_short(real, cfg, mesh):
    return real(dataclasses.replace(cfg, n_cycles=cfg.n_cycles - 1), mesh)


@pytest.mark.parametrize(
    "fault",
    [
        pytest.param(_returns_input, id="step_returns_its_input"),
        pytest.param(_in_bfloat16, id="solve_in_bfloat16"),
        pytest.param(_one_cycle_short, id="one_iteration_short"),
    ],
)
def test_exec_fault_turns_correct_false(cell, monkeypatch, fault):
    _break_solve(monkeypatch, fault)
    line = _run(cell("amg-table3.exec1", n_cycles=4))
    assert not line["correct"]
    c = line["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


def test_exec_without_region_scopes_is_not_correct(cell, monkeypatch):
    from repro.apps import amg

    monkeypatch.setattr(amg, "comm_region", lambda name: contextlib.nullcontext())
    line = _run(cell("amg-table3.exec1"))
    assert not line["correct"]
    assert line["checks"]["missing_region_scopes"]["value"] == 4
    assert line["checks"]["max_rel_err"]["value"] < 1e-5


def test_turnaround_without_the_coarse_gather_is_not_correct(cell, monkeypatch):
    """The coarse ``all_gather`` runs but is left out of the trace."""
    from jax import lax

    from repro.core import collectives

    monkeypatch.setattr(collectives, "all_gather", lax.all_gather)
    line = _run(cell("amg-table3.turnaround"))
    assert not line["correct"]
    assert line["checks"]["profile_fields_differing"]["value"] >= 2


def test_turnaround_answer_altered_is_not_correct(cell, monkeypatch):
    from repro.core.profiler import CommPatternProfiler

    real = CommPatternProfiler.from_recorder

    def altered(rec, **kw):
        prof = real(rec, **kw)
        prof.regions["reduce_norm"].coll += 1
        return prof

    monkeypatch.setattr(CommPatternProfiler, "from_recorder", staticmethod(altered))
    line = _run(cell("amg-table3.turnaround"))
    assert not line["correct"]
    assert line["checks"]["profile_fields_differing"]["value"] >= 1


@pytest.mark.parametrize("workload", ["amg-table3.exec1", "amg-table3.turnaround"])
def test_a_program_without_pcg_cannot_run_the_cells(cell, monkeypatch, workload):
    """An AMG module that iterates otherwise (no ``pcg``) fails at once."""
    from repro.apps import amg

    monkeypatch.delattr(amg, "pcg")
    with pytest.raises(RuntimeError, match="PCG-AMG"):
        _run(cell(workload))

