"""Kripke analog — deterministic Sn transport sweep (KBA wavefront).

Kripke (paper §III-A) decomposes a 3-D spatial grid over ranks; the *sweep*
region propagates angular flux in dependency order across subdomains: each
wavefront stage, ranks on the active diagonal receive upwind faces, solve
their local block, and send downwind faces.  Its communication is highly
localized (3 partners for corner ranks, 6 in the interior — paper §IV-A) and
each communication phase carries one message per (direction-set × group-set)
pair (the paper observes 36).

TPU adaptation (DESIGN.md §2): MPI Kripke posts one Isend per (dirset,
groupset) face; on TPU the native choice is to *fuse* them into a single
ppermute per axis.  ``fuse_messages`` selects between the paper-faithful
message granularity (False — reproduces the 36-messages finding and lets the
profiler quantify aggregation) and the TPU-native fused default (True).

The local solve is the diamond-difference recurrence
``psi_i = (q_i + w * psi_{i-1}) / (sigma_t + w)`` applied along x, then y,
then z (operator-split).  It is a *linear* recurrence, so blocks chain
exactly across ranks through the exchanged faces — the distributed sweep is
bit-comparable to the single-domain reference.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.apps.stencil import AXIS_NAMES, Decomp3D, bwd_perm, fwd_perm
from repro.core import collectives as coll, comm_region, compat, profile_traced
from repro.core.profiler import CommProfile
from repro.core.regions import tag_structure

# Sweep order interleaves opposing corners so that even a 2-octant run
# exercises both directions of an axis (paper §IV-A: interior ranks have 6
# communication partners, corner ranks 3).
OCTANT_ORDER = (7, 0, 6, 1, 5, 2, 4, 3)


@dataclass(frozen=True)
class KripkeConfig:
    """Weak-scaling config: zones are per-rank (paper smallest 16x32x32)."""

    decomp: Decomp3D = field(default_factory=lambda: Decomp3D(2, 2, 2))
    nx: int = 16  # per-rank zones
    ny: int = 32
    nz: int = 32
    n_dirsets: int = 6
    n_groupsets: int = 6  # 6 x 6 = 36 messages per phase (paper §IV-A)
    dirs_per_set: int = 4
    groups_per_set: int = 4
    sigma_t: float = 1.0
    w: tuple = (0.4, 0.35, 0.25)  # directional weights (wx, wy, wz)
    n_octants: int = 1  # sweep corners to run (1..8)
    fuse_messages: bool = True  # TPU-native message aggregation
    dtype: str = "float32"

    @property
    def zones(self) -> tuple:
        return (self.nx, self.ny, self.nz)

    @property
    def angular(self) -> tuple:
        return (
            self.n_dirsets, self.n_groupsets, self.dirs_per_set, self.groups_per_set
        )


def _octant_signs(octant: int) -> tuple:
    return (1 if octant & 1 else -1, 1 if octant & 2 else -1, 1 if octant & 4 else -1)


@lru_cache(maxsize=None)
def _plane_step(axis: int, a: float):
    """Scan body of the in-place recurrence along ``axis``: overwrite plane
    ``i`` of the carried array with ``a * prev + b_i`` and carry that plane
    on.  One function per (axis, a), so JAX reuses its traced jaxpr wherever
    the shapes repeat."""

    def step(carry, i):
        psi, prev = carry
        start = _plane_start(psi.ndim, axis, i)
        plane = a * prev + lax.dynamic_slice(psi, start, prev.shape)
        return (lax.dynamic_update_slice(psi, plane, start), plane), None

    return step


def _plane_start(ndim: int, axis: int, i):
    # unsigned indices are not normalised for negative values: no extra ops
    zero = np.uint32(0)
    return (zero,) * axis + (i,) + (zero,) * (ndim - axis - 1)


def _axis_recurrence(src, inflow, axis: int, w: float, sig: float, sign: int):
    """psi_i = a * psi_{i-1} + b_i with a = w/(sig+w), b = src/(sig+w);
    descending directions sweep the axis in reverse.  ``inflow`` (the
    upwind face, size 1 along ``axis``) is psi_{-1}.  ``axis`` is 2, 3 or 4
    (x, y, z); the pass runs under the named scope ``kripke.scan_<x|y|z>``.

    A sequential loop along the axis: a block seeded with its upwind
    neighbour's face repeats the single-domain arithmetic exactly.  (A
    reversed ``lax.associative_scan`` computed wrong values on TPU v5e at
    the Tioga global problem, 6x6x32x64x64x4x4, with jax 0.9.0.)

    x and y overwrite each plane of ``b`` where it lies.  On TPU, XLA puts
    the last spatial axis (z, ahead of the small direction and group dims)
    in the tile's lanes, so a plane along it would be one lane of every
    tile; z keeps a scan that stacks its planes in a buffer of their own.
    """
    with jax.named_scope(f"kripke.scan_{AXIS_NAMES[axis - 2]}"):
        a = w / (sig + w)
        b = src / (sig + w)
        if axis < src.ndim - 3:
            planes = np.arange(src.shape[axis], dtype=np.uint32)[::sign]
            start = _plane_start(src.ndim, axis, planes[0])
            # the upwind plane outside the loop, so the carry has its type
            first = a * inflow + lax.dynamic_slice(b, start, inflow.shape)
            psi = lax.dynamic_update_slice(b, first, start)
            (psi, _), _ = lax.scan(_plane_step(axis, a), (psi, first), planes[1:])
            return psi
        b = jnp.moveaxis(b, axis, 0)
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


def _local_sweep(q, in_x, in_y, in_z, cfg: KripkeConfig, signs=(1, 1, 1)):
    """Operator-split diamond-difference solve of one local block.

    q, psi: (nds, ngs, nx, ny, nz, d, g).  in_*: upwind ghost faces with the
    swept dim of size 1.  Returns (psi, out_x, out_y, out_z); out faces are
    the downwind faces for the given sweep direction signs.
    """
    sig = cfg.sigma_t
    sx, sy, sz = signs
    psi = _axis_recurrence(q, in_x, 2, cfg.w[0], sig, sx)
    psi = _axis_recurrence(psi, in_y, 3, cfg.w[1], sig, sy)
    psi = _axis_recurrence(psi, in_z, 4, cfg.w[2], sig, sz)

    def out_face(p, axis, sign):
        idx = [slice(None)] * p.ndim
        idx[axis] = slice(-1, None) if sign > 0 else slice(0, 1)
        return p[tuple(idx)]

    return (psi, out_face(psi, 2, sx), out_face(psi, 3, sy), out_face(psi, 4, sz))


@lru_cache(maxsize=None)
def _active_pairs(dc: Decomp3D, stage: int, axis: int, signs):
    """Global-rank (src, dst) pairs logically active at one pass stage,
    as an ``(P, 2)`` int64 array.

    MPI Kripke only posts sends from ranks on the active plane of the
    current axis pass; the profiler records these while the TPU executes
    the full (dense) permute.  The active plane is a single coordinate
    slab along ``axis``, so the pair set is the row-major enumeration of
    the other two axes broadcast against the slab/neighbor offsets — no
    Python loop over ranks.

    Memoized: every (dirset x groupset) message of a phase and every
    octant revisiting the stage reuses the cached array (the recording
    path fingerprints it without mutating), so the pair set is built once
    per unique (decomp, stage, axis, signs).

    The result is tagged (``tag_structure``) with the generator key
    ``("kripke-plane", stage, axis, signs[axis])`` under extent
    ``dc.shape`` — the pair set depends on the *axis* sign only, so
    octants sharing a direction along ``axis`` normalize to one struct
    even though lru_cache holds distinct arrays per full sign tuple.
    """
    sizes = dc.shape
    step = 1 if signs[axis] > 0 else -1
    gen = ("kripke-plane", int(stage), int(axis), int(signs[axis]))
    c = stage if signs[axis] > 0 else sizes[axis] - 1 - stage
    nc = c + step
    if not (0 <= c < sizes[axis] and 0 <= nc < sizes[axis]):
        return tag_structure(np.zeros((0, 2), np.int64), gen, sizes)
    strides = (sizes[1] * sizes[2], sizes[2], 1)
    others = [i for i in range(3) if i != axis]
    oa, ob = others
    base = (
        np.arange(sizes[oa], dtype=np.int64)[:, None] * strides[oa]
        + np.arange(sizes[ob], dtype=np.int64)[None, :] * strides[ob]
    ).reshape(-1)
    src = base + c * strides[axis]
    out = np.stack([src, src + step * strides[axis]], axis=1)
    return tag_structure(np.ascontiguousarray(out), gen, sizes)


def _send_downwind(face, axis: int, cfg: KripkeConfig, stage: int, signs):
    """One communication phase along the sweep direction of one axis:
    fused (TPU-native) or per-(ds,gs) messages (paper-faithful 36/phase)."""
    dc = cfg.decomp
    n = dc.shape[axis]
    axis_name = AXIS_NAMES[axis]
    perm = fwd_perm(n) if signs[axis] > 0 else bwd_perm(n)
    rec = _active_pairs(dc, stage, axis, signs)
    if cfg.fuse_messages:
        return coll.ppermute(face, axis_name, perm, record_pairs=rec)
    nds, ngs = cfg.n_dirsets, cfg.n_groupsets
    cols = []
    for ds in range(nds):
        rows = []
        for gs in range(ngs):
            msg = coll.ppermute(
                face[ds : ds + 1, gs : gs + 1], axis_name, perm, record_pairs=rec
            )
            rows.append(msg)
        cols.append(jnp.concatenate(rows, axis=1))
    return jnp.concatenate(cols, axis=0)


def sweep_octant(q, cfg: KripkeConfig, octant: int = 7):
    """One sweep of the given octant.  Runs inside shard_map.

    Octant bits select the sweep direction per axis (bit set = ascending);
    octant 7 is the (+,+,+) corner sweep.  The operator-split recurrence is
    swept as three sequential axis passes; within each pass, ranks along the
    axis form a pipeline chained by downwind face exchanges — the per-axis
    wavefront of the KBA schedule (exactly matching the single-domain
    reference, block boundaries included).
    """
    dc = cfg.decomp
    signs = _octant_signs(octant)
    coords = {0: lax.axis_index("x"), 1: lax.axis_index("y"), 2: lax.axis_index("z")}

    psi = q
    for axis in (0, 1, 2):
        n = dc.shape[axis]
        t = coords[axis] if signs[axis] > 0 else n - 1 - coords[axis]
        fshape = list(psi.shape)
        fshape[2 + axis] = 1
        in_face = jnp.zeros(tuple(fshape), psi.dtype)
        new_psi = psi
        for stage in range(n):
            active = (t == stage)
            with comm_region("solve"):
                cand, out_face = _axis_solve(psi, in_face, axis, cfg, signs)
            new_psi = jnp.where(active, cand, new_psi)
            out_face = jnp.where(active, out_face, jnp.zeros_like(out_face))
            if stage == n - 1:
                break
            with comm_region("sweep_comm"):
                g = _send_downwind(out_face, axis, cfg, stage, signs)
            # a valid face arrives exactly once (senders are masked to zero
            # at all other stages), so accumulation preserves it
            in_face = in_face + g
        psi = new_psi
    return psi


def _axis_solve(src, inflow, axis: int, cfg: KripkeConfig, signs):
    """One axis of the operator-split recurrence + its downwind face."""
    sign = signs[axis]
    psi = _axis_recurrence(src, inflow, 2 + axis, cfg.w[axis], cfg.sigma_t, sign)
    idx = [slice(None)] * psi.ndim
    idx[2 + axis] = slice(-1, None) if sign > 0 else slice(0, 1)
    return psi, psi[tuple(idx)]


def make_source(cfg: KripkeConfig, *, global_shape: bool = False):
    """Deterministic smooth source term (per-rank local shape by default)."""
    nds, ngs, d, g = cfg.angular
    if global_shape:
        nx = cfg.nx * cfg.decomp.px
        ny = cfg.ny * cfg.decomp.py
        nz = cfg.nz * cfg.decomp.pz
    else:
        nx, ny, nz = cfg.zones
    shape = (nds, ngs, nx, ny, nz, d, g)
    idx = [jnp.arange(s, dtype=cfg.dtype) for s in shape]
    grids = jnp.meshgrid(*idx, indexing="ij")
    q = 1.0
    for i, gr in enumerate(grids):
        q = q + jnp.sin(0.1 * (i + 1) * gr)
    return q.astype(cfg.dtype)


def distributed_sweep(cfg: KripkeConfig, mesh):
    """jit-able global-array sweep over the given mesh."""
    spec = P(None, None, *AXIS_NAMES, None, None)

    def run(q):
        def inner(q):
            with comm_region("main"):
                out = jnp.zeros_like(q)
                for o in range(cfg.n_octants):
                    out = out + sweep_octant(q, cfg, OCTANT_ORDER[o])
                return out

        return compat.shard_map(inner, mesh=mesh, in_specs=spec, out_specs=spec)(q)

    return run


def reference_sweep(cfg: KripkeConfig):
    """Single-domain oracle: same recurrence on the undecomposed grid."""
    single = replace(cfg, decomp=Decomp3D(1, 1, 1))

    def run(q):
        shape = q.shape
        in_x = jnp.zeros((shape[0], shape[1], 1) + shape[3:], q.dtype)
        in_y = jnp.zeros(shape[:3] + (1,) + shape[4:], q.dtype)
        in_z = jnp.zeros(shape[:4] + (1,) + shape[5:], q.dtype)
        out = jnp.zeros_like(q)
        for o in range(cfg.n_octants):
            psi, *_ = _local_sweep(
                q, in_x, in_y, in_z, single, _octant_signs(OCTANT_ORDER[o])
            )
            out = out + psi
        return out

    return run


def profile(
    cfg: KripkeConfig, *, name: str = "kripke", meta: dict | None = None
) -> CommProfile:
    """Communication profile of one sweep at cfg's scale (trace-only)."""
    mesh = cfg.decomp.make_mesh(abstract=True)
    q = jax.ShapeDtypeStruct(
        (
            cfg.n_dirsets,
            cfg.n_groupsets,
            cfg.nx * cfg.decomp.px,
            cfg.ny * cfg.decomp.py,
            cfg.nz * cfg.decomp.pz,
            cfg.dirs_per_set,
            cfg.groups_per_set,
        ),
        cfg.dtype,
    )
    with cfg.decomp.topology():
        return profile_traced(
            distributed_sweep(cfg, mesh),
            q,
            name=name,
            meta=dict(meta or {}, app="kripke", decomp=cfg.decomp.shape),
        )
