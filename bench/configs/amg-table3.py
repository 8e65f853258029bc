"""amg-table3, the program side: AMG2023's PCG–AMG solve at the paper's
Table III rows, as a benchpark ``ExperimentSpec`` (turnaround) and as the
Dane (8, 8, 8) global problem run whole on a chip mesh (exec)."""

from __future__ import annotations

import math

import numpy as np

AXES = ("x", "y", "z")


def _amg():
    """The program's AMG module, which must solve by PCG–AMG (an AMG module
    without ``pcg`` runs another iteration, not this configuration)."""
    from repro.apps import amg

    if not hasattr(amg, "pcg"):
        raise RuntimeError("amg-table3 needs the PCG-AMG solve (repro.apps.amg.pcg)")
    return amg


def global_shape(cfg: dict) -> tuple:
    """The exec point's global grid."""
    return tuple(z * p for z, p in zip(cfg["zones_per_rank"], cfg["exec_point"]))


def _solver_params(cfg: dict, n_cycles: int) -> dict:
    keys = ("n_pre", "n_post", "n_coarse_iters", "omega", "min_global", "dtype")
    return dict({k: cfg[k] for k in keys}, n_cycles=n_cycles)


def spec(cfg: dict):
    """The weak-scaling experiment over the configuration's points, one
    traced PCG iteration (``traced_cycles``) a point."""
    from repro.benchpark.spec import ExperimentSpec, ScalePoint

    _amg()
    nx, ny, nz = cfg["zones_per_rank"]
    return ExperimentSpec(
        name=cfg["experiment"],
        app=cfg["app"],
        scaling=cfg["scaling"],
        points=tuple(ScalePoint(tuple(p)) for p in cfg["points"]),
        app_params=dict(
            nx=nx, ny=ny, nz=nz, **_solver_params(cfg, cfg["traced_cycles"])
        ),
    )


def app_config(cfg: dict, mesh_shape: tuple):
    """The program's config for the exec point's global problem on
    ``mesh_shape``, with the configuration's ``n_cycles``."""
    from repro.apps.stencil import Decomp3D

    amg = _amg()
    shape = global_shape(cfg)
    if any(s % m for s, m in zip(shape, mesh_shape)):
        raise ValueError(f"grid {shape} does not split over mesh {mesh_shape}")
    nx, ny, nz = (s // m for s, m in zip(shape, mesh_shape))
    return amg.AMGConfig(
        decomp=Decomp3D(*mesh_shape),
        nx=nx,
        ny=ny,
        nz=nz,
        **_solver_params(cfg, cfg["n_cycles"]),
    )


def input_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(*AXES))


def seed32(seed: int) -> int:
    """A 32-bit key word from any whole-number seed."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])


def make_input(cfg: dict, seed: int, sharding):
    """The right-hand side, uniform in [0.5, 1.5), drawn on the device from
    ``seed`` in one jitted call."""
    import jax

    shape, dtype = global_shape(cfg), np.dtype(cfg["dtype"])

    def draw(key):
        return jax.random.uniform(key, shape, dtype, 0.5, 1.5)

    return jax.jit(draw, out_shardings=sharding)(jax.random.key(seed32(seed)))


def program(cfg: dict, mesh):
    """The solve (regions on) over ``mesh``, its two outputs packed into the
    one array the exec traffic reads back: ``u`` flattened, then the
    relative residual norm."""
    import jax.numpy as jnp

    solve = _amg().solve(app_config(cfg, tuple(mesh.devices.shape)), mesh)

    def run(f):
        u, relres = solve(f)
        return jnp.concatenate([u.reshape(-1), relres.reshape(1)])

    return run


def region_scopes(cfg: dict, mesh_shape: tuple) -> set:
    """The ``commr::`` scopes the compiled program must hold: the solve, the
    inner products, the matvec halos and each distributed level's smoother
    halos (on one chip their exchanges compile to zero ghosts that keep the
    scope); the coarse gather only where some mesh axis spans more than one
    chip (on one it is the identity and compiles away)."""
    levels = len(_level_sizes(cfg)[0])
    scopes = {"main", "reduce_norm", "MatVecComm"}
    scopes |= {f"mg_level_{k}" for k in range(levels)}
    if math.prod(mesh_shape) > 1:
        scopes.add("coarse_solve")
    return scopes


def _level_sizes(cfg: dict) -> tuple:
    """Global points of each distributed level, and of the coarse level."""
    shape, sizes = global_shape(cfg), []
    while min(shape) // 2 >= cfg["min_global"]:
        sizes.append(math.prod(shape))
        shape = tuple(s // 2 for s in shape)
    return sizes, math.prod(shape)


def compulsory_bytes(cfg: dict, n_chips: int) -> int:
    """HBM bytes of one solve on each chip, each pass reading its operands
    once and writing its result once: at each distributed level of n points
    a Jacobi sweep or the residual moves 3n values (e, r in; e out),
    restriction n + n/8, prolongation with its correction 2n + n/8; at the
    coarse level each sweep 3n.  Each PCG iteration adds the matvec (2n),
    the inner product (p, q) (2n) and the updates of u and r (3n each); each
    but the last its V-cycle, (r, z) (2n) and the update of p (3n).  Before
    the loop (f, f) (n), the first V-cycle and (r, z) (2n); after it
    (r, r) (n)."""
    sizes, n_coarse = _level_sizes(cfg)
    sweeps = cfg["n_pre"] + cfg["n_post"]
    per_level = 3 * (sweeps + 1) + (1 + 1 / 8) + (2 + 1 / 8)
    v_cycle = sum(per_level * n for n in sizes) + 3 * cfg["n_coarse_iters"] * n_coarse
    n0, it = math.prod(global_shape(cfg)), cfg["n_cycles"]
    values = n0 + v_cycle + 2 * n0 + n0
    values += it * (2 + 2 + 3 + 3) * n0 + (it - 1) * (v_cycle + 2 * n0 + 3 * n0)
    return int(values * np.dtype(cfg["dtype"]).itemsize) // n_chips
