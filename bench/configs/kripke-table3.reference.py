"""kripke-table3, the plain reference: Kripke's Table-I statistics at one
decomposition, counted from the sweep's definition in NumPy, sharing no
code with the program.

The sweep visits octants in a fixed order (bit set: ascending along that
axis).  For each octant and each axis of ``n`` ranks it solves ``n`` stages
(``solve`` instances) and exchanges downwind faces ``n - 1`` times
(``sweep_comm`` instances).  Over one axis pass every rank that is not last
along the sweep direction sends its downwind face once to its neighbour, as
one fused message or one message per (direction-set, group-set); a face is
``sets x zones of the face x directions x groups`` values.  The rank grid
is not periodic, so each (axis, direction) a rank sends in is a distinct
peer.  ``main`` holds the sweep and communicates nothing itself.
"""

from __future__ import annotations

import numpy as np

OCTANT_ORDER = (7, 0, 6, 1, 5, 2, 4, 3)


def _stats(
    region: str, instances: int, ranks: dict, largest: int, kinds: dict, int_dtype
) -> dict:
    sends, bsent = ranks["sends"], ranks["bytes_sent"]
    total_sends = int(sends.sum(dtype=int_dtype))
    total_bytes = int(bsent.sum(dtype=int_dtype))
    out = {"region": region, "instances": instances}
    for key in ranks:
        out[key] = [int(ranks[key].min()), int(ranks[key].max())]
    out.update(
        coll=0,
        coll_bytes=[0, 0],
        total_bytes_sent=total_bytes,
        total_sends=total_sends,
        largest_send=largest,
        n_ranks=len(sends),
        kinds=kinds,
        avg_send_size=total_bytes / total_sends if total_sends else 0.0,
    )
    return out


def profile(cfg: dict, decomp, int_dtype=np.int64) -> dict:
    """``{"n_ranks", "regions"}`` of one point, as the profile's JSON holds
    them.  Totals are summed in ``int_dtype`` (the control sums in int32)."""
    decomp = tuple(int(p) for p in decomp)
    n = int(np.prod(decomp))
    zones = cfg["zones_per_rank"]
    sets = cfg["n_dirsets"] * cfg["n_groupsets"]
    per_phase = 1 if cfg["fuse_messages"] else sets
    value = cfg["dirs_per_set"] * cfg["groups_per_set"]
    value *= np.dtype(cfg["dtype"]).itemsize
    face_zones = (zones[1] * zones[2], zones[0] * zones[2], zones[0] * zones[1])
    msg_bytes = [sets // per_phase * f * value for f in face_zones]

    coords = np.indices(decomp).reshape(3, -1)  # row-major rank order
    zero = np.zeros(n, np.int64)
    sends, recvs, bsent, brecv = zero.copy(), zero.copy(), zero.copy(), zero.copy()
    out_peer, in_peer = {}, {}
    solve = comm = 0
    largest = 0
    for octant in OCTANT_ORDER[: cfg["n_octants"]]:
        for axis in range(3):
            na = decomp[axis]
            up = bool(octant >> axis & 1)
            solve += na
            if na < 2:
                continue
            comm += na - 1
            c = coords[axis]
            s = (c < na - 1) if up else (c > 0)
            r = (c > 0) if up else (c < na - 1)
            sends += per_phase * s
            recvs += per_phase * r
            bsent += per_phase * msg_bytes[axis] * s
            brecv += per_phase * msg_bytes[axis] * r
            out_peer[axis, up] = out_peer.get((axis, up), False) | s
            in_peer[axis, up] = in_peer.get((axis, up), False) | r
            largest = max(largest, msg_bytes[axis])
    ranks = {
        "sends": sends,
        "recvs": recvs,
        "dest_ranks": sum(out_peer.values(), zero),
        "src_ranks": sum(in_peer.values(), zero),
        "bytes_sent": bsent,
        "bytes_recv": brecv,
    }
    quiet = {k: zero for k in ranks}
    regions = {
        "main": _stats("main", 1, quiet, 0, {}, int_dtype),
        "solve": _stats("solve", solve, quiet, 0, {}, int_dtype),
    }
    if comm:
        kinds = {"ppermute": comm * per_phase}
        regions["sweep_comm"] = _stats(
            "sweep_comm", comm, ranks, largest, kinds, int_dtype
        )
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
