"""kripke-tioga, the program side: the Kripke sweep of the paper's Tioga
(2, 2, 2) point, run whole on a chip mesh, and its work count."""

from __future__ import annotations

import math

import numpy as np

AXES = ("x", "y", "z")


def global_shape(cfg: dict) -> tuple:
    zones = [z * r for z, r in zip(cfg["zones_per_rank"], cfg["rank_decomp"])]
    return (
        cfg["n_dirsets"],
        cfg["n_groupsets"],
        *zones,
        cfg["dirs_per_set"],
        cfg["groups_per_set"],
    )


def app_config(cfg: dict, mesh_shape: tuple):
    """The program's config for the global problem on ``mesh_shape``."""
    from repro.apps.kripke import KripkeConfig
    from repro.apps.stencil import Decomp3D

    zones = global_shape(cfg)[2:5]
    if any(z % m for z, m in zip(zones, mesh_shape)):
        raise ValueError(f"zones {zones} do not split over mesh {mesh_shape}")
    nx, ny, nz = (z // m for z, m in zip(zones, mesh_shape))
    return KripkeConfig(
        decomp=Decomp3D(*mesh_shape),
        nx=nx,
        ny=ny,
        nz=nz,
        n_dirsets=cfg["n_dirsets"],
        n_groupsets=cfg["n_groupsets"],
        dirs_per_set=cfg["dirs_per_set"],
        groups_per_set=cfg["groups_per_set"],
        sigma_t=cfg["sigma_t"],
        w=tuple(cfg["w"]),
        n_octants=cfg["n_octants"],
        fuse_messages=cfg["fuse_messages"],
        dtype=cfg["dtype"],
    )


def input_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec(None, None, *AXES, None, None))


def seed32(seed: int) -> int:
    """A 32-bit key word from any whole-number seed."""
    return int(np.random.SeedSequence(seed % 2**64).generate_state(1)[0])


def make_input(cfg: dict, seed: int, sharding):
    """The source, drawn on the device from ``seed`` in one jitted call."""
    import jax

    shape, dtype = global_shape(cfg), np.dtype(cfg["dtype"])

    def draw(key):
        return jax.random.uniform(key, shape, dtype, 0.5, 1.5)

    return jax.jit(draw, out_shardings=sharding)(jax.random.key(seed32(seed)))


def program(cfg: dict, mesh):
    """The app's jit-able entry (regions on) over ``mesh``."""
    from repro.apps.kripke import distributed_sweep

    return distributed_sweep(app_config(cfg, tuple(mesh.devices.shape)), mesh)


def region_scopes(cfg: dict, mesh_shape: tuple) -> set:
    """The ``commr::`` scopes the compiled program must hold: the sweep's
    exchange exists only where some mesh axis spans more than one chip."""
    scopes = {"main", "solve"}
    if math.prod(mesh_shape) > 1:
        scopes.add("sweep_comm")
    return scopes


def compulsory_bytes(cfg: dict, n_chips: int) -> int:
    """Least HBM bytes of one sweep on each chip: the source read once and
    the result written once."""
    n = math.prod(global_shape(cfg)) * np.dtype(cfg["dtype"]).itemsize
    return 2 * n // n_chips
