"""AMG2023 analog — conjugate gradients preconditioned by a multigrid V-cycle,
with per-level communication regions.

AMG2023 (paper §III-A) solves a Poisson problem with hypre's conjugate
gradients preconditioned by BoomerAMG V-cycles; its communication is a
hierarchy of halo exchanges whose character changes with the multigrid
level — fine levels move the most data between few neighbors, coarse levels
involve many ranks with little data (paper Figs. 2-3, over 100 source ranks
at MG level 6+ on 512 processes) — plus the Krylov method's global inner
products.

We build the geometric analog on the same block decomposition the paper
uses.  The problem is ``A u = f`` with ``A = -Δ`` (7-point, h = 1, zero
Dirichlet boundary).  Level k has spacing ``h_k = 2^k`` and operator
``A_k = -Δ / h_k²``; distributed levels coarsen by 2 (restriction: the mean
of each 2x2x2 block; prolongation: piecewise constant, so restriction is
prolongation's transpose over 8) while every *global* dim stays
≥ ``min_global``; below that the residual is gathered to every rank and
solved redundantly by ``n_coarse_iters`` weighted-Jacobi sweeps from zero
(``coarse_solve`` — the all-ranks participation the paper observes at
coarse levels).  One V-cycle from a zero guess, with as many pre- as
post-smoothing weighted-Jacobi sweeps, is a fixed symmetric positive
definite preconditioner; ``solve`` runs ``n_cycles`` iterations of
preconditioned conjugate gradients, one V-cycle and one matvec each.

Regions:
  mg_level_<k>   smoother halo exchanges on level k (Figs. 2-3)
  MatVecComm     matvec halos: the Krylov operator and each level's
                 residual (hypre's MatVecComm analog, paper §III-B)
  coarse_solve   the all-rank gather of the coarse residual
  reduce_norm    the Krylov inner products and norms (psums)

Device phases carry ``jax.named_scope``s: ``amg.smooth``, ``amg.residual``,
``amg.restrict``, ``amg.prolong``, ``amg.coarse``, ``amg.matvec`` and
``amg.krylov``.

Weak scaling mirrors the paper: per-rank fine block fixed (default 32x32x16),
global problem grows with ranks — note more ranks ⇒ a deeper hierarchy,
matching "runs on Dane had more levels".
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.apps.stencil import (AXIS_NAMES, Decomp3D, halo_exchange,
                                laplacian_7pt, pad_with_halo)
from repro.core import collectives as coll, comm_region, compat, profile_traced
from repro.core.profiler import CommProfile


@dataclass(frozen=True)
class AMGConfig:
    decomp: Decomp3D = field(default_factory=lambda: Decomp3D(2, 2, 2))
    nx: int = 32          # per-rank fine-grid block (paper: 32x32x16)
    ny: int = 32
    nz: int = 16
    n_pre: int = 2        # pre-smoothing sweeps
    n_post: int = 2       # post-smoothing sweeps (as many as pre: symmetric)
    n_coarse_iters: int = 8
    omega: float = 0.8    # weighted-Jacobi damping
    min_global: int = 8   # gather when a *global* dim would drop below this
    n_cycles: int = 1     # PCG iterations, one V-cycle each
    dtype: str = "float32"

    @property
    def local_shape(self) -> tuple:
        return (self.nx, self.ny, self.nz)

    @property
    def global_shape(self) -> tuple:
        return (self.nx * self.decomp.px, self.ny * self.decomp.py,
                self.nz * self.decomp.pz)

    def n_dist_levels(self) -> int:
        """Distributed levels before the gathered coarse solve.

        Level count depends on the *global* grid so the distributed solver
        and the single-domain reference run identical hierarchies — and more
        ranks (weak scaling) means more levels, as the paper observes."""
        n, lvl = min(self.global_shape), 0
        while n // 2 >= self.min_global:
            n //= 2
            lvl += 1
        return lvl


def _matvec(u, cfg: AMGConfig, level: int, region: str):
    """``A_k u = -Δu / h_k²`` with the halo exchanged in ``region``."""
    with comm_region(region):
        ghosts = halo_exchange(u, cfg.decomp)
    return -laplacian_7pt(pad_with_halo(u, ghosts), h2=4.0**level)


def _jacobi(u, f, cfg: AMGConfig, level: int):
    """One weighted-Jacobi sweep on level k: ``u += ω D_k⁻¹ (f - A_k u)``,
    ``D_k = 6 / h_k²``."""
    with jax.named_scope("amg.smooth"):
        au = _matvec(u, cfg, level, f"mg_level_{level}")
        return u + (cfg.omega * 4.0**level / 6.0) * (f - au)


def _restrict(r):
    """2x coarsening by the mean of each 2x2x2 block (local: blocks stay
    rank-aligned) — prolongation's transpose over 8.

    A window sum at stride 2 keeps ``r`` in its own layout; splitting each
    axis into a size-2 dim instead puts 2 in a TPU tile's 128 lanes and pads
    the residual 64x."""
    return lax.reduce_window(r, 0.0, lax.add, (2, 2, 2), (2, 2, 2), "VALID") * 0.125


def _prolong(e):
    """Piecewise-constant 2x refinement (local)."""
    return jnp.repeat(jnp.repeat(jnp.repeat(e, 2, 0), 2, 1), 2, 2)


def _gather_global(x, cfg: AMGConfig):
    """all_gather a per-rank block into the replicated global array."""
    dc = cfg.decomp
    g = coll.all_gather(x, AXIS_NAMES, axis=0)     # (n_ranks, lx, ly, lz)
    lx, ly, lz = x.shape
    g = g.reshape(dc.px, dc.py, dc.pz, lx, ly, lz)
    g = g.transpose(0, 3, 1, 4, 2, 5)
    return g.reshape(dc.px * lx, dc.py * ly, dc.pz * lz)


def _my_block(g, local_shape):
    ix = lax.axis_index("x")
    iy = lax.axis_index("y")
    iz = lax.axis_index("z")
    lx, ly, lz = local_shape
    return lax.dynamic_slice(g, (ix * lx, iy * ly, iz * lz), (lx, ly, lz))


def _coarse_solve(r, cfg: AMGConfig, level: int):
    """Gather the coarse residual to every rank and solve ``A_k e = r``
    redundantly by ``n_coarse_iters`` weighted-Jacobi sweeps from zero: a
    fixed polynomial in ``A_k``, so linear and symmetric.

    This is the all-ranks-involved pattern the paper measures at coarse MG
    levels (src ranks ≈ everyone, little data).
    """
    with comm_region("coarse_solve"):
        rg = _gather_global(r, cfg)
    with jax.named_scope("amg.coarse"):
        h2 = 4.0**level
        e = jnp.zeros_like(rg)
        for _ in range(cfg.n_coarse_iters):
            ae = -laplacian_7pt(jnp.pad(e, 1), h2=h2)
            e = e + (cfg.omega * h2 / 6.0) * (rg - ae)
        return _my_block(e, r.shape)


def v_cycle(r, cfg: AMGConfig, level: int = 0):
    """One V-cycle from a zero guess on ``A_k e = r``: the preconditioner."""
    global_min = min(s * p for s, p in zip(r.shape, cfg.decomp.shape))
    if global_min // 2 < cfg.min_global:
        return _coarse_solve(r, cfg, level)
    e = jnp.zeros_like(r)
    for _ in range(cfg.n_pre):
        e = _jacobi(e, r, cfg, level)
    with jax.named_scope("amg.residual"):
        res = r - _matvec(e, cfg, level, "MatVecComm")
    with jax.named_scope("amg.restrict"):
        r_c = _restrict(res)
    e_c = v_cycle(r_c, cfg, level + 1)
    with jax.named_scope("amg.prolong"):
        e = e + _prolong(e_c)
    for _ in range(cfg.n_post):
        e = _jacobi(e, r, cfg, level)
    return e


def _dot(a, b):
    """Global inner product: a local sum and one psum over every rank."""
    with comm_region("reduce_norm"):
        return coll.psum(jnp.vdot(a, b), AXIS_NAMES)


def pcg(f, cfg: AMGConfig):
    """``n_cycles`` iterations of V-cycle-preconditioned conjugate gradients
    on ``A u = f`` from ``u = 0``, inside ``shard_map``.  Returns ``u`` and
    the relative residual norm ``‖r‖/‖f‖`` of the *recursive* residual (the
    one the iteration updates, which saves a final matvec and its halo).

    The iterations are unrolled, as the levels are, so a trace counts each.
    """
    with jax.named_scope("amg.krylov"):
        ff = _dot(f, f)
        u, r = jnp.zeros_like(f), f
    z = v_cycle(r, cfg)
    with jax.named_scope("amg.krylov"):
        p, rz = z, _dot(r, z)
    for k in range(cfg.n_cycles):
        with jax.named_scope("amg.matvec"):
            q = _matvec(p, cfg, 0, "MatVecComm")
        with jax.named_scope("amg.krylov"):
            alpha = rz / _dot(p, q)
            u = u + alpha * p
            r = r - alpha * q
        if k + 1 < cfg.n_cycles:
            z = v_cycle(r, cfg)
            with jax.named_scope("amg.krylov"):
                rz, rz_old = _dot(r, z), rz
                p = z + (rz / rz_old) * p
    with jax.named_scope("amg.krylov"):
        return u, jnp.sqrt(_dot(r, r) / ff)


def solve(cfg: AMGConfig, mesh):
    """jit-able PCG–AMG solve of ``A u = f`` on global arrays: returns the
    solution and its relative (recursive) residual norm."""
    spec = P(*AXIS_NAMES)

    def run(f):
        def inner(f):
            with comm_region("main"):
                return pcg(f, cfg)
        return compat.shard_map(inner, mesh=mesh, in_specs=spec,
                                out_specs=(spec, P()))(f)
    return run


def reference_solve(cfg: AMGConfig):
    """Single-domain oracle (identical arithmetic on the global grid)."""
    single = replace(cfg, decomp=Decomp3D(1, 1, 1),
                     nx=cfg.nx * cfg.decomp.px,
                     ny=cfg.ny * cfg.decomp.py,
                     nz=cfg.nz * cfg.decomp.pz)
    mesh = single.decomp.make_mesh()
    return solve(single, mesh), single


def make_rhs(cfg: AMGConfig):
    """Deterministic smooth RHS on the global grid."""
    nx = cfg.nx * cfg.decomp.px
    ny = cfg.ny * cfg.decomp.py
    nz = cfg.nz * cfg.decomp.pz
    x, y, z = jnp.meshgrid(jnp.arange(nx), jnp.arange(ny), jnp.arange(nz),
                           indexing="ij")
    f = (jnp.sin(2 * jnp.pi * x / nx) * jnp.sin(2 * jnp.pi * y / ny)
         * jnp.sin(2 * jnp.pi * z / nz))
    return f.astype(cfg.dtype)


def profile(cfg: AMGConfig, *, name: str = "amg",
            meta: dict | None = None) -> CommProfile:
    mesh = cfg.decomp.make_mesh(abstract=True)
    f = jax.ShapeDtypeStruct(
        (cfg.nx * cfg.decomp.px, cfg.ny * cfg.decomp.py,
         cfg.nz * cfg.decomp.pz), cfg.dtype)
    with cfg.decomp.topology():
        return profile_traced(solve(cfg, mesh), f, name=name,
                              meta=dict(meta or {}, app="amg",
                                        decomp=cfg.decomp.shape))
