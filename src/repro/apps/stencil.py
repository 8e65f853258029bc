"""Domain decomposition + halo-exchange machinery shared by the three apps.

The paper's three benchmarks (AMG2023, Kripke, Laghos) are all domain-
decomposed codes whose dominant communication pattern is the halo (ghost-
cell) exchange.  On TPU the native point-to-point primitive is
``lax.ppermute`` over a mesh axis of the ICI torus; a 3-D halo exchange is
six ppermutes (±x, ±y, ±z) — exactly the kind of logical group the paper's
communication regions were designed to bracket.

Everything here runs *inside* ``shard_map`` and uses the instrumented
collectives so profiling sees it.  All mesh / shard_map construction is
routed through :mod:`repro.core.compat`, the one module that touches
version-sensitive JAX APIs (see its module docstring for the contract).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import collectives as coll
from repro.core import compat
from repro.core.topology import topology


AXIS_NAMES = ("x", "y", "z")


@dataclass(frozen=True)
class Decomp3D:
    """A px × py × pz process decomposition."""

    px: int
    py: int
    pz: int

    @property
    def shape(self) -> tuple:
        return (self.px, self.py, self.pz)

    @property
    def n_ranks(self) -> int:
        return self.px * self.py * self.pz

    def axes(self) -> tuple:
        return tuple(zip(AXIS_NAMES, self.shape))

    def topology(self):
        return topology(*self.axes())

    def make_mesh(self, abstract: bool = False):
        """Real mesh (needs devices) or abstract mesh (trace-only)."""
        if abstract:
            return compat.abstract_mesh(self.shape, AXIS_NAMES)
        return compat.make_mesh(self.shape, AXIS_NAMES)

    def spec(self, extra_dims: int = 0) -> P:
        return P(*AXIS_NAMES, *([None] * extra_dims))


def fwd_perm(n: int, periodic: bool = False) -> list:
    """(i -> i+1) pairs; edge pair dropped unless periodic (Dirichlet ghost)."""
    pairs = [(i, i + 1) for i in range(n - 1)]
    if periodic and n > 1:
        pairs.append((n - 1, 0))
    return pairs


def bwd_perm(n: int, periodic: bool = False) -> list:
    pairs = [(i + 1, i) for i in range(n - 1)]
    if periodic and n > 1:
        pairs.append((0, n - 1))
    return pairs


def _face(u: jnp.ndarray, dim: int, side: str, width: int) -> jnp.ndarray:
    idx = [slice(None)] * u.ndim
    idx[dim] = slice(0, width) if side == "lo" else slice(-width, None)
    return u[tuple(idx)]


def halo_exchange(u: jnp.ndarray, decomp: Decomp3D, *, width: int = 1,
                  dims: tuple = (0, 1, 2), periodic: bool = False) -> dict:
    """Exchange ghost faces along each decomposed dimension.

    Returns {dim: (ghost_lo, ghost_hi)}: ``ghost_lo`` is the neighbor's high
    face arriving at our low side, and vice versa.  Edge ranks receive zeros
    (homogeneous Dirichlet ghosts) in the non-periodic case — ppermute
    delivers zeros where no pair targets a rank.

    Call inside shard_map, inside a ``comm_region``.
    """
    sizes = decomp.shape
    out = {}
    for dim in dims:
        n = sizes[dim]
        axis = AXIS_NAMES[dim]
        hi_face = _face(u, dim, "hi", width)   # travels to the right (+)
        lo_face = _face(u, dim, "lo", width)   # travels to the left  (-)
        ghost_lo = coll.ppermute(hi_face, axis, fwd_perm(n, periodic))
        ghost_hi = coll.ppermute(lo_face, axis, bwd_perm(n, periodic))
        out[dim] = (ghost_lo, ghost_hi)
    return out


def pad_with_halo(u: jnp.ndarray, ghosts: dict, *, width: int = 1,
                  dims: tuple = (0, 1, 2)) -> jnp.ndarray:
    """Concatenate exchanged ghosts onto u → array padded by `width` on the
    exchanged dims (ghosts of ghost corners are zero; adequate for 7-point
    stencils which never read corners)."""
    for dim in dims:
        lo, hi = ghosts[dim]
        pad_shape = list(u.shape)
        pad_shape[dim] = width
        # lo/hi were sliced from the *unpadded* array; pad their other dims
        # to match the progressively padded u.
        def fit(g):
            pads = []
            for d in range(u.ndim):
                diff = u.shape[d] - g.shape[d]
                pads.append((0, 0) if d == dim else (diff // 2, diff - diff // 2))
            pads[dim] = (0, 0)
            return jnp.pad(g, pads)
        u = jnp.concatenate([fit(lo), u, fit(hi)], axis=dim)
    return u


def laplacian_7pt(u_padded: jnp.ndarray, h2: float = 1.0) -> jnp.ndarray:
    """7-point Laplacian of interior (expects width-1 padding on dims 0-2)."""
    c = u_padded[1:-1, 1:-1, 1:-1]
    return (u_padded[:-2, 1:-1, 1:-1] + u_padded[2:, 1:-1, 1:-1]
            + u_padded[1:-1, :-2, 1:-1] + u_padded[1:-1, 2:, 1:-1]
            + u_padded[1:-1, 1:-1, :-2] + u_padded[1:-1, 1:-1, 2:]
            - 6.0 * c) / h2


def run_sharded(fn, decomp: Decomp3D, mesh, in_specs, out_specs):
    """shard_map wrapper (the deprecation boundary lives in compat)."""
    return compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs)
