"""amg-table3, the plain reference, sharing no code with the program: the
PCG–AMG solve written out in NumPy from its definition, and the Table-I
statistics of one traced PCG iteration at a decomposition, counted from the
same definition.

The solve.  ``A = -Δ`` (7-point, zero Dirichlet boundary, h = 1) on the
global grid.  Level k has ``h_k² = 4^k`` and ``A_k = -Δ / h_k²``.  A level
whose smallest global dim halved would fall below ``min_global`` is the
coarse level: there ``A_k e = r`` is solved by ``n_coarse_iters`` sweeps of
weighted Jacobi from zero, ``e += ω h_k²/6 (r - A_k e)``.  Any other level
runs the V-cycle from ``e = 0``: ``n_pre`` such sweeps, the residual
``r - A_k e``, restriction by the mean of each 2x2x2 block, the V-cycle of
level k + 1 on that, the correction prolonged piecewise constant, and
``n_post`` sweeps.  PCG from ``u = 0`` runs ``n_cycles`` iterations, one
V-cycle ``z = M r`` and one matvec ``q = A p`` each:
``alpha = (r, z) / (p, q)``, ``u += alpha p``, ``r -= alpha q``, then (but
after the last) ``z = M r`` and ``p = z + (r, z)_new / (r, z)_old p``.  The
output is ``u`` and the recursive relative residual ``‖r‖ / ‖f‖``.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from functools import reduce

import numpy as np

#: NumPy releases the GIL inside its loops, so slabs of the leading axis run
#: side by side.  The slabs change nothing but the order in which an inner
#: product adds its partial sums.  The pool starts its threads on first use.
_THREADS = min(16, os.cpu_count() or 1)
_POOL = ThreadPoolExecutor(_THREADS)


def _slabs(fn, n: int) -> list:
    """``fn(lo, hi)`` over slabs of ``range(n)`` with even bounds, in
    threads; their results in order."""
    k = max(1, min(_THREADS, n // 2))
    edges = [2 * (n // 2 * i // k) for i in range(k)] + [n]
    return list(_POOL.map(fn, edges[:-1], edges[1:]))


def _matvec_rows(out, u, lo, hi, h2, dt):
    """``out[lo:hi] = -Δu / h²`` on rows ``lo:hi``, zero Dirichlet ghosts."""
    n, o = len(u), out[lo:hi]
    np.multiply(u[lo:hi], dt.type(6.0), out=o)
    a, b = max(lo, 1), min(hi, n - 1)
    o[a - lo :] -= u[a - 1 : hi - 1]  # x - 1
    o[: b - lo] -= u[lo + 1 : b + 1]  # x + 1
    o[:, 1:] -= u[lo:hi, :-1]
    o[:, :-1] -= u[lo:hi, 1:]
    o[:, :, 1:] -= u[lo:hi, :, :-1]
    o[:, :, :-1] -= u[lo:hi, :, 1:]
    if h2 != 1.0:
        o *= dt.type(1.0 / h2)


def _matvec(u, h2, dt):
    out = np.empty_like(u)
    _slabs(lambda lo, hi: _matvec_rows(out, u, lo, hi, h2, dt), len(u))
    return out


def _jacobi(e, r, h2, cfg, dt):
    """``e + ω h²/6 (r - A e)``, a new array."""
    out, c = np.empty_like(e), dt.type(cfg["omega"] * h2 / 6.0)

    def rows(lo, hi):
        _matvec_rows(out, e, lo, hi, h2, dt)
        o = out[lo:hi]
        np.subtract(r[lo:hi], o, out=o)
        o *= c
        o += e[lo:hi]

    _slabs(rows, len(e))
    return out


def _v_cycle(r, level, cfg, dt):
    h2 = 4.0**level
    e = np.zeros_like(r)
    if min(r.shape) // 2 < cfg["min_global"]:
        for _ in range(cfg["n_coarse_iters"]):
            e = _jacobi(e, r, h2, cfg, dt)
        return e
    for _ in range(cfg["n_pre"]):
        e = _jacobi(e, r, h2, cfg, dt)
    nx, ny, nz = (s // 2 for s in r.shape)
    res, r_c = np.empty_like(r), np.empty((nx, ny, nz), dt)

    def restrict(lo, hi):  # the residual, then the mean of each 2x2x2 block
        _matvec_rows(res, e, lo, hi, h2, dt)
        np.subtract(r[lo:hi], res[lo:hi], out=res[lo:hi])
        block = res[lo:hi].reshape((hi - lo) // 2, 2, ny, 2, nz, 2)
        r_c[lo // 2 : hi // 2] = block.sum(axis=(1, 3, 5)) * dt.type(0.125)

    _slabs(restrict, len(r))
    e_c = _v_cycle(r_c, level + 1, cfg, dt)

    def prolong(lo, hi):  # piecewise-constant correction
        fine = e[lo:hi].reshape((hi - lo) // 2, 2, ny, 2, nz, 2)
        fine += e_c[lo // 2 : hi // 2, None, :, None, :, None]

    _slabs(prolong, len(r))
    for _ in range(cfg["n_post"]):
        e = _jacobi(e, r, h2, cfg, dt)
    return e


def _dot(a, b):
    parts = _slabs(lambda lo, hi: (a[lo:hi] * b[lo:hi]).sum(), len(a))
    return reduce(lambda x, y: x + y, parts)


def _axpy(y, alpha, x):
    """``y += alpha x`` in place."""

    def rows(lo, hi):
        y[lo:hi] += alpha * x[lo:hi]

    _slabs(rows, len(y))


def solve(cfg: dict, f: np.ndarray, dtype=np.float64, n_cycles=None):
    """``(u, relres)`` of ``n_cycles`` (default the configuration's) PCG–AMG
    iterations on ``A u = f``, every operation in ``dtype``."""
    dt = np.dtype(dtype)
    n = cfg["n_cycles"] if n_cycles is None else n_cycles
    f = f.astype(dt)
    u, r = np.zeros_like(f), f.copy()
    z = _v_cycle(r, 0, cfg, dt)
    p, rz = z, _dot(r, z)
    for k in range(n):
        q = _matvec(p, 1.0, dt)
        alpha = rz / _dot(p, q)
        _axpy(u, alpha, p)
        _axpy(r, -alpha, q)
        if k + 1 < n:
            z = _v_cycle(r, 0, cfg, dt)
            rz, rz_old = _dot(r, z), rz
            p *= rz / rz_old
            _axpy(p, dt.type(1.0), z)
    return u, np.sqrt(_dot(r, r) / _dot(f, f))


def true_relres(f: np.ndarray, u: np.ndarray) -> float:
    """``‖f - A u‖ / ‖f‖`` in float64, the residual that fixes ``n_cycles``."""
    f, u = f.astype(np.float64), u.astype(np.float64)
    r = f - _matvec(u, 1.0, np.dtype(np.float64))
    return float(np.sqrt(_dot(r, r) / _dot(f, f)))


def unpack(cfg: dict, out: np.ndarray):
    """The program's one output array: ``u`` on the exec point's global grid,
    flattened, then the relative residual norm."""
    shape = [z * p for z, p in zip(cfg["zones_per_rank"], cfg["exec_point"])]
    return out[:-1].reshape(shape), out[-1]


def max_rel_err(cfg: dict, q: np.ndarray, outputs: list, dtype=np.float64):
    """The larger of two gaps of each output from the float64 solve of
    ``q``: ``max|u - u_ref| / max|u_ref|``, and ``|relres - relres_ref|``.
    The second is a gap of two residuals already in units of ``‖f‖``; it is
    not divided by ``relres_ref``, since float32 floors a recursive residual
    near 5e-6 (its rounding), where the float64 one reads 8e-7.  The
    reference is solved once (every step has the same input).  With
    ``dtype`` below float64 the reference itself is solved in it and read
    against the float64 solve, in place of ``outputs`` (the control)."""
    u_ref, rr_ref = solve(cfg, q)
    if np.dtype(dtype) != np.float64:
        outputs = [np.append(*solve(cfg, q, dtype))]
    worst = 0.0
    for out in outputs:
        u, rr = unpack(cfg, np.asarray(out, np.float64))
        err_u = np.abs(u - u_ref).max() / np.abs(u_ref).max()
        err_r = abs(rr - rr_ref)
        err = float(max(err_u, err_r))
        if not np.isfinite(err):
            return float("inf")
        worst = max(worst, err)
    return worst


# --- Table-I statistics of the traced solve --------------------------------
#
# The turnaround traces ``traced_cycles`` PCG iterations a point: V-cycles
# ``N = traced_cycles`` (the first before the loop, one in every iteration
# but the last), matvecs ``N``, inner products ``2 N + 2``.  A halo on level
# k exchanges the six faces of the rank's level-k block (32x32x16 / 2^k): one
# ``ppermute`` call per dim and direction, each rank sending its face to the
# neighbour that exists that way (the rank grid is not periodic), so a rank
# has one peer per neighbour.  ``mg_level_<k>`` holds ``n_pre + n_post``
# halos per V-cycle; ``MatVecComm`` one per distributed level per V-cycle
# (the residual) and one per matvec on level 0; ``coarse_solve`` one
# all-rank ``all_gather`` of the coarse block per V-cycle, counted as
# ``(n - 1)`` times its bytes per rank; ``reduce_norm`` one ``psum`` of one
# value per inner product, counted as ``2 (n - 1) / n`` times its bytes,
# whole bytes.  ``main`` holds the solve and communicates nothing itself.


def _levels(cfg: dict, decomp) -> int:
    shape, levels = [z * p for z, p in zip(cfg["zones_per_rank"], decomp)], 0
    while min(shape) // 2 >= cfg["min_global"]:
        shape = [s // 2 for s in shape]
        levels += 1
    return levels


def _region(name, instances, n, int_dtype, p2p=None, coll=(0, 0, {})):
    zero = np.zeros(n, np.int64)
    p2p = p2p or dict.fromkeys(
        ("sends", "recvs", "dest_ranks", "src_ranks", "bytes_sent", "bytes_recv"),
        zero,
    )
    p2p = dict(p2p)
    largest, kinds = p2p.pop("largest", 0), dict(p2p.pop("kinds", {}))
    n_coll, coll_bytes, coll_kinds = coll
    kinds.update(coll_kinds)
    total_sends = int(p2p["sends"].sum(dtype=int_dtype))
    total_bytes = int(p2p["bytes_sent"].sum(dtype=int_dtype))
    out = {"region": name, "instances": instances}
    for key, v in p2p.items():
        out[key] = [int(v.min()), int(v.max())]
    out.update(
        coll=n_coll,
        coll_bytes=[coll_bytes, coll_bytes],
        total_bytes_sent=total_bytes,
        total_sends=total_sends,
        largest_send=largest,
        n_ranks=n,
        kinds=kinds,
        avg_send_size=total_bytes / total_sends if total_sends else 0.0,
    )
    return out


def _halos(cfg: dict, decomp, coords, counts: dict) -> dict:
    """Per-rank statistics of ``counts[k]`` halos on each level k."""
    n = coords.shape[1]
    itemsize = np.dtype(cfg["dtype"]).itemsize
    stats = {k: np.zeros(n, np.int64) for k in ("sends", "bytes_sent")}
    peers = np.zeros(n, np.int64)
    largest = 0
    for d in range(3):
        # one face each way along d: to the + neighbour, to the - neighbour
        for has in (coords[d] < decomp[d] - 1, coords[d] > 0):
            peers += has
            for level, halos in counts.items():
                block = [z // 2**level for z in cfg["zones_per_rank"]]
                face = int(np.prod(block)) // block[d] * itemsize
                stats["sends"] += halos * has
                stats["bytes_sent"] += halos * face * has
                if halos and has.any():
                    largest = max(largest, face)
    # the pattern is symmetric: each face sent one way is received the other
    return dict(
        sends=stats["sends"],
        recvs=stats["sends"],
        dest_ranks=peers,
        src_ranks=peers,
        bytes_sent=stats["bytes_sent"],
        bytes_recv=stats["bytes_sent"],
        largest=largest,
        kinds={"ppermute": 6 * sum(counts.values())},
    )


def profile(cfg: dict, decomp, int_dtype=np.int64) -> dict:
    """``{"n_ranks", "regions"}`` of one point, as the profile's JSON holds
    them.  Totals are summed in ``int_dtype`` (the control sums in int32)."""
    decomp = tuple(int(p) for p in decomp)
    n = int(np.prod(decomp))
    coords = np.indices(decomp).reshape(3, -1)  # row-major rank order
    cycles, levels = cfg["traced_cycles"], _levels(cfg, decomp)
    itemsize = np.dtype(cfg["dtype"]).itemsize
    sweeps = cfg["n_pre"] + cfg["n_post"]
    regions = {"main": _region("main", 1, n, int_dtype)}
    for k in range(levels):
        name = f"mg_level_{k}"
        p2p = _halos(cfg, decomp, coords, {k: cycles * sweeps})
        regions[name] = _region(name, cycles * sweeps, n, int_dtype, p2p)
    matvecs = {k: cycles for k in range(levels)}
    matvecs[0] = matvecs.get(0, 0) + cycles
    p2p = _halos(cfg, decomp, coords, matvecs)
    regions["MatVecComm"] = _region(
        "MatVecComm", cycles * (levels + 1), n, int_dtype, p2p
    )
    coarse_block = int(np.prod([z // 2**levels for z in cfg["zones_per_rank"]]))
    gather = cycles * int(coarse_block * itemsize * (n - 1))
    coll = (cycles, gather, {"all_gather": cycles})
    regions["coarse_solve"] = _region("coarse_solve", cycles, n, int_dtype, coll=coll)
    dots = 2 * cycles + 2
    psum = dots * int(itemsize * (2 * (n - 1) / n))
    coll = (dots, psum, {"psum": dots})
    regions["reduce_norm"] = _region("reduce_norm", dots, n, int_dtype, coll=coll)
    return {"n_ranks": n, "regions": regions}


def _leaves(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _leaves(v, path + (k,))
    else:
        yield path, tree


def fields_differing(got: dict, want: dict) -> int:
    """Leaves of ``want`` that ``got`` lacks or holds another value for,
    plus leaves ``got`` has beyond ``want``."""
    g, w = dict(_leaves(got)), dict(_leaves(want))
    return sum(g.get(k, object()) != v for k, v in w.items()) + len(g.keys() - w.keys())
