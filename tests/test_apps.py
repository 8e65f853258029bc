"""The paper's benchmarks: comm-pattern findings + numerics.

Covers the paper's three apps (kripke / amg / laghos) plus the
Beatnik-style global-communication mini-app that stresses the trace
substrate's worst case (all-rank far-field coupling, per-step structure
mutation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from helpers import run_with_devices

from repro.apps.amg import (
    AMGConfig, _prolong, _restrict, make_rhs, profile as amg_profile, solve
)
from repro.apps.beatnik import BeatnikConfig, _migration, profile as beatnik_profile
from repro.apps.kripke import KripkeConfig, _axis_recurrence, profile as kripke_profile
from repro.apps.laghos import (
    LaghosConfig, make_state, profile as laghos_profile, run_steps
)
from repro.apps.stencil import Decomp3D


# ---------------------------------------------------------------------------
# Kripke — paper §IV-A findings
# ---------------------------------------------------------------------------


def test_kripke_corner_vs_interior_partners():
    """Corner ranks have 3 communication partners, interior 6 (paper)."""
    cfg = KripkeConfig(
        decomp=Decomp3D(4, 4, 4), nx=4, ny=4, nz=4, n_octants=2, fuse_messages=False
    )
    p = kripke_profile(cfg)
    sc = p.regions["sweep_comm"]
    assert sc.dest_ranks == (3, 6)
    assert sc.src_ranks == (3, 6)


def test_kripke_36_messages_per_phase():
    """6 dirsets x 6 groupsets = 36 messages to each partner per phase."""
    cfg = KripkeConfig(
        decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4, n_octants=1, fuse_messages=False
    )
    p = kripke_profile(cfg)
    sc = p.regions["sweep_comm"]
    # the first corner rank sends 36 msgs to each of its 3 partners
    assert sc.sends[1] == 36 * 3


def test_kripke_message_aggregation_knob():
    """Fused (TPU-native) mode moves identical bytes in 36x fewer messages."""
    base = dict(decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4, n_octants=1)
    unfused = kripke_profile(KripkeConfig(fuse_messages=False, **base))
    fused = kripke_profile(KripkeConfig(fuse_messages=True, **base))
    u, f = unfused.regions["sweep_comm"], fused.regions["sweep_comm"]
    assert u.total_bytes_sent == f.total_bytes_sent
    assert u.total_sends == 36 * f.total_sends


def test_kripke_weak_scaling_constant_per_rank_bytes():
    """Paper Table IV: Kripke per-rank comm stays ~constant under weak
    scaling (largest send constant)."""
    sizes = {}
    for shape in [(2, 2, 2), (4, 4, 4)]:
        cfg = KripkeConfig(decomp=Decomp3D(*shape), nx=4, ny=4, nz=4)
        sizes[shape] = kripke_profile(cfg).regions["sweep_comm"].largest_send
    assert sizes[(2, 2, 2)] == sizes[(4, 4, 4)]


def test_kripke_distributed_matches_reference_8ranks():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.apps.kripke import (KripkeConfig, distributed_sweep,
                                       make_source, reference_sweep)
        from repro.apps.stencil import Decomp3D
        cfg = KripkeConfig(decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4,
                           n_dirsets=2, n_groupsets=2, dirs_per_set=2,
                           groups_per_set=2, n_octants=3)
        mesh = cfg.decomp.make_mesh()
        q = make_source(cfg, global_shape=True)
        out = distributed_sweep(cfg, mesh)(q)
        ref = reference_sweep(cfg)(q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def _stacked_scan_recurrence(src, inflow, axis, w, sig, sign):
    """The recurrence as a scan that stacks its planes: the axis moved to
    the front, ``lax.scan``, the upwind plane concatenated, the axis moved
    back (the form every axis used before x and y ran in place)."""
    a = w / (sig + w)
    b = jnp.moveaxis(src / (sig + w), axis, 0)
    inflow = jnp.moveaxis(inflow, axis, 0)[0]

    def step(prev, b_i):
        psi = a * prev + b_i
        return psi, psi

    # the upwind zone seeds the carry, so it has the type of the rows
    if sign > 0:
        first = a * inflow + b[0]
        _, rest = lax.scan(step, first, b[1:])
        psi = jnp.concatenate([first[None], rest])
    else:
        first = a * inflow + b[-1]
        _, rest = lax.scan(step, first, b[:-1], reverse=True)
        psi = jnp.concatenate([rest, first[None]])
    return jnp.moveaxis(psi, 0, axis)


@pytest.mark.parametrize("sign", [1, -1])
@pytest.mark.parametrize("axis", [2, 3, 4])
def test_kripke_axis_recurrence_matches_stacked_scan_and_float64(axis, sign):
    """Every axis and direction gives bitwise the stacked scan's result
    under jit, and the float64 loop's to 1e-6, from a nonzero inflow on
    planes that are not whole tiles."""
    shape = (2, 3, 5, 6, 7, 4, 4)
    w, sig = 0.35, 1.0
    rng = np.random.default_rng(axis * 10 + sign)
    src = rng.uniform(0.5, 1.5, shape).astype(np.float32)
    face = list(shape)
    face[axis] = 1
    inflow = rng.uniform(0.5, 1.5, face).astype(np.float32)

    args = (src, inflow, axis, w, sig, sign)
    static = dict(static_argnums=(2, 3, 4, 5))
    got = np.asarray(jax.jit(_axis_recurrence, **static)(*args))
    want = np.asarray(jax.jit(_stacked_scan_recurrence, **static)(*args))
    np.testing.assert_array_equal(got, want)

    a = w / (sig + w)
    b = np.moveaxis(src.astype(np.float64) / (sig + w), axis, 0)
    prev = np.moveaxis(inflow.astype(np.float64), axis, 0)[0]
    ref = np.empty_like(b)
    for i in range(len(b)) if sign > 0 else reversed(range(len(b))):
        prev = ref[i] = a * prev + b[i]
    np.testing.assert_allclose(got, np.moveaxis(ref, 0, axis), rtol=1e-6)


# ---------------------------------------------------------------------------
# AMG — paper §IV-B findings
# ---------------------------------------------------------------------------


def test_amg_bytes_decrease_with_level():
    """Paper Fig 2: fine levels carry the most data."""
    p = amg_profile(AMGConfig(decomp=Decomp3D(2, 2, 2)))
    b0 = p.regions["mg_level_0"].bytes_sent[1]
    b1 = p.regions["mg_level_1"].bytes_sent[1]
    assert b0 > b1 > 0


def test_amg_coarse_level_involves_everyone():
    """Paper Fig 3 / §IV-B: coarse levels broaden to all ranks."""
    p = amg_profile(AMGConfig(decomp=Decomp3D(2, 2, 2)))
    fine = p.regions["mg_level_0"]
    coarse = p.regions["coarse_solve"]
    assert fine.dest_ranks[1] <= 6
    assert coarse.coll >= 1  # gather involves the full communicator
    assert coarse.coll_bytes[1] > 0


def test_amg_vcycle_converges():
    """The V-cycle-preconditioned CG solve of the smooth RHS on a 32x32x16
    grid reaches a relative residual of 1e-5 in 10 iterations, as the solve
    reports it and recomputed in float64 from ``u`` (float32 rounding of
    ``u`` floors the latter near 3e-6 here).  The stationary V-cycle it
    replaced reads 0.067 after 10 cycles."""
    cfg = AMGConfig(decomp=Decomp3D(1, 1, 1), nx=32, ny=32, nz=16, n_cycles=10)
    f = make_rhs(cfg)
    u, relres = jax.jit(solve(cfg, cfg.decomp.make_mesh()))(f)
    u, f = np.asarray(u, np.float64), np.asarray(f, np.float64)
    p = np.pad(u, 1)
    au = 6 * u - (p[:-2, 1:-1, 1:-1] + p[2:, 1:-1, 1:-1] + p[1:-1, :-2, 1:-1]
                  + p[1:-1, 2:, 1:-1] + p[1:-1, 1:-1, :-2] + p[1:-1, 1:-1, 2:])
    true = np.linalg.norm(f - au) / np.linalg.norm(f)
    assert float(relres) <= 1e-5 and true <= 1e-5


#: Each level's shape of the Dane hierarchy scaled down, and one non-cubic.
RESTRICT_SHAPES = [(32, 32, 16), (16, 16, 8), (8, 8, 4), (6, 20, 10)]


@pytest.mark.parametrize("shape", RESTRICT_SHAPES)
def test_amg_restrict_is_the_block_mean(shape):
    """The restriction is the mean of each 2x2x2 block: the float64 NumPy
    mean of the same float32 values, to float32 rounding."""
    r = np.random.default_rng(sum(shape)).standard_normal(shape).astype(np.float32)
    got = jax.jit(_restrict)(jnp.asarray(r))
    assert got.dtype == jnp.float32
    x, y, z = shape
    ref = r.astype(np.float64).reshape(x // 2, 2, y // 2, 2, z // 2, 2)
    np.testing.assert_allclose(np.asarray(got), ref.mean(axis=(1, 3, 5)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", RESTRICT_SHAPES)
def test_amg_restrict_is_prolongation_transposed_over_8(shape):
    """``<R r, e_c> · 8 == <r, P e_c>``: restriction and prolongation are
    each other's transposes up to 8, which keeps the V-cycle symmetric."""
    rng = np.random.default_rng(len(shape) + sum(shape))
    r = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    e_c = jnp.asarray(rng.standard_normal([n // 2 for n in shape]), jnp.float32)
    lhs = 8 * jnp.vdot(jax.jit(_restrict)(r), e_c)
    rhs = jnp.vdot(r, jax.jit(_prolong)(e_c))
    scale = 4 * np.sqrt(r.size)  # |<r, P e_c>| for unit normals is ~ sqrt(8 n)
    assert abs(float(lhs) - float(rhs)) <= 1e-5 * scale


def test_amg_distributed_matches_reference_8ranks():
    run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.apps.amg import AMGConfig, make_rhs, solve, reference_solve
        from repro.apps.stencil import Decomp3D
        cfg = AMGConfig(decomp=Decomp3D(2, 2, 2), nx=8, ny=8, nz=8, n_cycles=4)
        mesh = cfg.decomp.make_mesh()
        f = make_rhs(cfg)
        u, rn = jax.jit(solve(cfg, mesh))(f)
        ref_run, ref_cfg = reference_solve(cfg)
        u_ref, rn_ref = jax.jit(ref_run)(f)
        np.testing.assert_allclose(np.asarray(u), np.asarray(u_ref),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(rn), float(rn_ref), rtol=1e-4)
        print("OK")
    """)


# ---------------------------------------------------------------------------
# Laghos — paper §IV-C findings
# ---------------------------------------------------------------------------


def test_laghos_strong_scaling_bytes_per_rank_decrease():
    """Paper: data volume per rank goes down as scale goes up (strong)."""
    b = {}
    for px in (4, 8, 16):  # interior ranks exist from 4x4 up
        cfg = LaghosConfig(decomp=Decomp3D(px, px, 1), nx=64, ny=64, n_steps=1)
        b[px] = laghos_profile(cfg).regions["halo_exchange"].bytes_sent[1]
    assert b[4] > b[8] > b[16]


def test_laghos_timestep_has_reduce_and_broadcast():
    cfg = LaghosConfig(decomp=Decomp3D(2, 2, 1), nx=32, ny=32, n_steps=1)
    p = laghos_profile(cfg)
    ts = p.regions["timestep"]
    assert ts.coll == 2
    assert set(ts.kinds) == {"pmin", "broadcast"}


def test_laghos_distributed_matches_reference_8ranks():
    run_with_devices("""
        import numpy as np, jax
        from repro.apps.laghos import (LaghosConfig, make_state, run_steps,
                                       reference_steps)
        from repro.apps.stencil import Decomp3D
        cfg = LaghosConfig(decomp=Decomp3D(4, 2, 1), nx=32, ny=32, n_steps=3)
        mesh = cfg.decomp.make_mesh()
        state = make_state(cfg)
        out, dts = run_steps(cfg, mesh)(state)
        ref, dts_ref = reference_steps(cfg)(state)
        for k in out:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(ref[k]),
                                       rtol=5e-5, atol=5e-6)
        np.testing.assert_allclose(np.asarray(dts), np.asarray(dts_ref),
                                   rtol=1e-5)
        print("OK")
    """)


def test_laghos_energy_stays_finite():
    cfg = LaghosConfig(decomp=Decomp3D(1, 1, 1), nx=64, ny=64, n_steps=5)
    mesh = cfg.decomp.make_mesh()
    out, dts = run_steps(cfg, mesh)(make_state(cfg))
    assert bool(jnp.isfinite(out["e"]).all())
    assert bool((np.asarray(dts) > 0).all())


# ---------------------------------------------------------------------------
# Beatnik — global far-field coupling + per-step structure mutation
# ---------------------------------------------------------------------------


def test_beatnik_far_field_couples_all_ranks():
    """The far-field all-gather involves every rank, every step — the
    adversarial opposite of the halo apps' constant-degree traffic."""
    cfg = BeatnikConfig(
        decomp=Decomp3D(4, 4, 1), nx=8, ny=8, far_subsample=8, n_steps=2
    )
    p = beatnik_profile(cfg)
    ff = p.regions["far_field"]
    assert ff.coll == cfg.n_steps
    assert set(ff.kinds) == {"all_gather"}
    # every rank contributes bytes to the global gather
    assert all(b > 0 for b in ff.coll_bytes)


def test_beatnik_migration_mutates_structure_per_step():
    """The migration permute's (axis, shift) never repeats within an axis
    cycle, so consecutive steps intern fresh structures (the dedup worst
    case the lazy store is benchmarked against)."""
    cfg = BeatnikConfig(
        decomp=Decomp3D(4, 4, 1), nx=8, ny=8, far_subsample=8, n_steps=6
    )
    seen = [_migration(cfg, s) for s in range(cfg.n_steps)]
    assert len(set(seen)) == len(seen)  # all distinct
    assert {axis for axis, _ in seen} == {0, 1}
    p = beatnik_profile(cfg)
    mig = p.regions["migrate"]
    # two permutes (z and w) per migrating step
    assert mig.total_sends == 2 * cfg.n_steps * cfg.decomp.n_ranks


def test_beatnik_single_rank_axis_skips_migration():
    """A 1-wide migration axis has nowhere to shift: _migration degrades
    to a no-op instead of a self-permute."""
    cfg = BeatnikConfig(
        decomp=Decomp3D(4, 1, 1), nx=8, ny=8, far_subsample=8, n_steps=4
    )
    assert _migration(cfg, 1) == (1, 0)  # y axis is 1 wide
    p = beatnik_profile(cfg)
    mig = p.regions["migrate"]
    # only the even (x-axis) steps migrate
    assert mig.total_sends == 2 * (cfg.n_steps // 2) * cfg.decomp.n_ranks


def test_beatnik_distributed_matches_reference_8ranks():
    run_with_devices("""
        import numpy as np
        from repro.apps.beatnik import (BeatnikConfig, make_state, run_steps,
                                        reference_steps)
        from repro.apps.stencil import Decomp3D
        cfg = BeatnikConfig(decomp=Decomp3D(4, 2, 1), nx=8, ny=8,
                            far_subsample=8, n_steps=3)
        mesh = cfg.decomp.make_mesh()
        state = make_state(cfg)
        (z, w), nrms = run_steps(cfg, mesh)(state)
        (zr, wr), nrms_ref = reference_steps(cfg)(state)
        np.testing.assert_allclose(np.asarray(z), np.asarray(zr),
                                   rtol=5e-5, atol=5e-6)
        np.testing.assert_allclose(np.asarray(w), np.asarray(wr),
                                   rtol=5e-5, atol=5e-6)
        np.testing.assert_allclose(np.asarray(nrms), np.asarray(nrms_ref),
                                   rtol=1e-4)
        print("OK")
    """)
