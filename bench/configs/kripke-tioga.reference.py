"""kripke-tioga, the plain reference: the sweep written out in NumPy from
its definition, sharing no code with the program.

For each octant in the sweep order, psi starts as the source q and is swept
along x, then y, then z (operator split): along an axis,
``psi_i = a * psi_{i-1} + q_i / (sigma_t + w)`` with ``a = w / (sigma_t +
w)`` and ``psi_{-1} = 0`` at the global boundary, ascending where the
octant's bit for that axis is set and descending where it is not.  The
result is the sum over octants.  It runs one (direction-set, group-set)
block at a time, so it holds a block, not the whole problem, in float64.
"""

from __future__ import annotations

import numpy as np

#: Octants in the order the sweep visits them (bit set: ascending).
OCTANT_ORDER = (7, 0, 6, 1, 5, 2, 4, 3)


def _recurrence(src, axis: int, w: float, sig: float, ascending: bool, dtype):
    a = dtype(w / (sig + w))
    b = np.moveaxis(src, axis, 0) / dtype(sig + w)
    psi = np.empty_like(b)
    prev = np.zeros(b.shape[1:], dtype)
    for i in range(len(b)) if ascending else range(len(b) - 1, -1, -1):
        prev = a * prev + b[i]
        psi[i] = prev
    return np.moveaxis(psi, 0, axis)


def sweep_blocks(cfg: dict, q: np.ndarray, dtype=np.float64):
    """Yield ``((ds, gs), psi)`` for each block of the sweep of ``q``,
    computed in ``dtype``."""
    sig, w = cfg["sigma_t"], cfg["w"]
    for ds in range(q.shape[0]):
        for gs in range(q.shape[1]):
            src = q[ds, gs].astype(dtype)
            total = np.zeros(src.shape, dtype)
            for octant in OCTANT_ORDER[: cfg["n_octants"]]:
                psi = src
                for axis in range(3):
                    up = bool(octant >> axis & 1)
                    psi = _recurrence(psi, axis, w[axis], sig, up, dtype)
                total = total + psi
            yield (ds, gs), total


def max_rel_err(cfg: dict, q: np.ndarray, outputs: list, dtype=np.float64):
    """Largest relative gap, over every element of every array in
    ``outputs``, from the sweep of ``q`` computed in float64.  With
    ``dtype`` below float64 the reference itself is computed in it and
    read against the float64 sweep, in place of ``outputs`` (the control)."""
    worst = 0.0
    control = np.dtype(dtype) != np.float64
    low = sweep_blocks(cfg, q, dtype) if control else None
    for (ds, gs), want in sweep_blocks(cfg, q):
        gots = [next(low)[1]] if control else [o[ds, gs] for o in outputs]
        for got in gots:
            rel = np.abs(got.astype(np.float64) - want) / np.abs(want)
            err = float(rel.max())
            if not np.isfinite(err):
                return float("inf")
            worst = max(worst, err)
    return worst
