"""Backend-abstracted reduction substrate shared by every analysis layer.

The profilers (traced-layer :class:`~repro.core.profiler.CommPatternProfiler`,
compiled-layer :class:`~repro.core.profiler.HloCollectiveProfiler`) and the
vectorized :class:`~repro.core.thicket.Frame` reductions all bottom out in a
small set of kernels:

* :func:`segment_spans` — ordering + contiguous block boundaries for
  grouped segment reductions (host-side NumPy; shared by every backend);
* ``block_reduce`` / ``segment_reduce`` — per-segment reductions over 2-D
  grids / 1-D columns;
* ``matmul`` — the (region x struct) multiplicity-weighted **exact int64**
  weight matmuls against the StructTable's dense (struct x rank) slabs;
* ``pair_counts`` — the distinct-peer-set dedup over encoded
  (region, rank, peer) codes;
* ``factorize`` — ``np.unique(return_index, return_inverse)`` semantics for
  Frame group codes.

Two interchangeable implementations with **bit-identical** outputs:

``NumpyBackend``
    The reference: plain NumPy, the historical hot path.  ``pair_counts``
    picks between one dense bitmap scatter, a *chunked* bitmap scatter over
    region groups (bounding peak allocation to :data:`_BITMAP_CELLS_CAP`
    cells at high rank counts), and a sort-based ``np.unique`` pass when the
    code space is sparse relative to the pair count — see
    :func:`_dedup_strategy`.

``JaxBackend``
    Reductions on the default jax device.  Exact int64 matmuls run as one
    int8 x int8 -> int32 device dot over signed 7-bit limbs of both
    operands (every partial sum is an exact int32), recombined on the host
    modulo 2**64.  ``block_reduce`` / ``segment_reduce`` run a **Pallas
    segmented-reduce kernel** on TPU: 32-bit tiles on a (limb, column,
    row) grid, sums as one-hot bf16 matmuls over 8-bit limbs on the MXU,
    max/min as masked column reductions (see
    :func:`_pallas_segment_reduce` for how exactness is kept).  Off TPU
    they run XLA's ``segment_*`` ops, or the kernel in interpret mode when
    a CPU test asks for it.  x64 is scoped to the calls that move int64
    through XLA (``compat.enable_x64``), never enabled process-wide, and
    never reaches the kernel.

Boundary contract (what the profilers rely on):

* NumPy in, NumPy out — every method accepts and returns ``np.ndarray``;
  device residency is a backend-internal detail.
* int64 count/byte paths are **exact**, never rounded: results are
  bit-identical across backends whenever the true values fit in int64.
* Small scatters (``np.add.at`` weight accumulation), argsorts, and
  ``reduceat`` calls with O(rows) inputs stay host-side even under the jax
  backend — measured on CPU, XLA scatter/sort lose to NumPy there, while
  the weight-grid matmuls (the O(G*S*Rmax) term that dominates at high
  rank counts) win by a wide margin.

Selection: :func:`resolve_backend` resolves, in priority order, an explicit
``backend=`` argument (name or instance), a :func:`use_backend` thread-local
override, the ``REPRO_BACKEND`` environment variable, and finally
``"numpy"``.  Nothing falls back: asking for jax when it is missing or x64
cannot be enabled raises :class:`BackendUnavailable`, and an unknown name
raises ``ValueError``.
"""

from __future__ import annotations

import functools
import os
import threading
from contextlib import contextmanager
from typing import Optional, Union

import numpy as np

from repro.core.tracing import span

#: Environment variable naming the default reduction backend.
BACKEND_ENV = "REPRO_BACKEND"

#: Dense dedup bitmaps never allocate more than this many boolean cells at
#: once; past it the scatter chunks over region groups (or falls back to the
#: sort-based path) — see :func:`_dedup_strategy`.
_BITMAP_CELLS_CAP = 1 << 26

#: Dense bitmaps touch every cell; past this work factor relative to the
#: pair count, one sort of the pair codes is cheaper than zeroing+summing
#: the full (group, rank, peer) code space.
_BITMAP_WORK_FACTOR = 64

#: Past this rank extent the sort-based fallback first *compacts* the rank
#: and peer id spaces (``np.unique`` sketch of the ids actually present) and
#: re-decides the strategy on the compacted extents: structured traces touch
#: a thin slice of the rank space per struct (a kripke plane, a halo face),
#: so the dense scatter paths usually re-engage where the raw code space was
#: hopelessly sparse — see the ``("hybrid", 0)`` branch of
#: :func:`_dedup_strategy`.
_SKETCH_RANK_EXTENT = 1 << 16

#: Low PAIR_CODE_SHIFT bits of a fixed pair code (the peer field).
_PAIR_CODE_MASK = (1 << 32) - 1


# ---------------------------------------------------------------------------
# Shared host-side kernels (every backend uses these)
# ---------------------------------------------------------------------------


def segment_spans(key: np.ndarray) -> tuple:
    """Ordering + contiguous block boundaries for segment reductions.

    ``key`` holds one composite int group code per element.  Returns
    ``(order, sorted_key, starts, ends)``: ``order`` is None when the input
    is already non-decreasing (the common, pre-grouped trace shape — the
    permutation is skipped entirely), otherwise a stable argsort; block
    ``i`` of the sorted data spans ``starts[i]:ends[i]`` and carries key
    ``sorted_key[starts[i]]``.
    """
    n = len(key)
    if n == 0:
        z = np.zeros(0, np.int64)
        return None, np.asarray(key), z, z
    if np.any(np.diff(key) < 0):
        order = np.argsort(key, kind="stable")
        sorted_key = key[order]
    else:
        order = None
        sorted_key = key
    starts = np.concatenate(([0], np.flatnonzero(np.diff(sorted_key)) + 1))
    ends = np.append(starts[1:], n)
    return order, sorted_key, starts, ends


def block_reduce(
    grid: np.ndarray, starts: np.ndarray, ends: np.ndarray, ufunc: np.ufunc
) -> np.ndarray:
    """One contiguous block reduction per segment over a 2-D grid's rows.

    ``ufunc.reduce`` over a contiguous block vectorizes along the inner
    axis where generic ``reduceat`` falls back to a scalar inner loop; the
    block count is O(groups), not O(rows).  This is the NumPy reference —
    backends may route it elsewhere (see :meth:`JaxBackend.block_reduce`).
    """
    return np.stack([ufunc.reduce(grid[s:e], axis=0) for s, e in zip(starts, ends)])


def segment_reduce(
    col: np.ndarray, order, starts: np.ndarray, ufunc: np.ufunc = np.add
) -> np.ndarray:
    """Per-segment reduction of a 1-D column in one ``reduceat`` pass.

    ``order`` / ``starts`` come from :func:`segment_spans` over the
    column's group codes.  NumPy reference implementation.
    """
    if not len(starts):
        return np.zeros(0, col.dtype)
    vals = col if order is None else col[order]
    return ufunc.reduceat(vals, starts)


def _segment_ids(starts: np.ndarray, n: int) -> np.ndarray:
    """Per-element segment id for contiguous spans tiling ``[0, n)``."""
    nseg = len(starts)
    lengths = np.diff(np.append(starts, n))
    return np.repeat(np.arange(nseg, dtype=np.int64), lengths)


# ---------------------------------------------------------------------------
# Peer-set dedup strategy (satellite of the backend refactor: the dense
# G * Rmax * stride bitmap went quadratic-ish at high rank counts)
# ---------------------------------------------------------------------------


def _dedup_strategy(n_groups: int, rank_extent: int, stride: int, m: int) -> tuple:
    """Pick the distinct-peer dedup path for ``m`` encoded pairs.

    Returns ``("bitmap", n_groups)`` for one dense scatter over the whole
    (group, rank, peer) code space, ``("chunked", groups_per_chunk)`` for
    dense scatters over group chunks whose bitmaps stay under
    :data:`_BITMAP_CELLS_CAP` cells, ``("hybrid", 0)`` to compact the
    rank/peer id spaces first and re-decide on the compacted extents
    (engages past :data:`_SKETCH_RANK_EXTENT` ranks, where the raw code
    space is hopelessly sparse but the ids actually present are usually a
    thin structured slice), or ``("unique", 0)`` for the sort-based path.
    Dense scatters touch every cell, so they only run when the code space
    is within :data:`_BITMAP_WORK_FACTOR` cells per pair; the chunking
    keeps peak allocation bounded at rank counts where the historical
    single bitmap (``cells = G * Rmax * stride``, with ``stride ~ Rmax``)
    grew quadratically.  All paths produce identical counts.
    """
    per_group = int(rank_extent) * int(stride)
    cells = int(n_groups) * per_group
    if m == 0 or cells == 0:
        return ("unique", 0)
    sparse_fallback = (
        ("hybrid", 0) if rank_extent > _SKETCH_RANK_EXTENT else ("unique", 0)
    )
    if cells > _BITMAP_WORK_FACTOR * m:
        return sparse_fallback
    if cells <= _BITMAP_CELLS_CAP:
        return ("bitmap", int(n_groups))
    if per_group <= _BITMAP_CELLS_CAP:
        return ("chunked", max(1, _BITMAP_CELLS_CAP // per_group))
    return sparse_fallback


def _compact_ids(col: np.ndarray) -> tuple:
    """Presence-mask id compaction: ``(uniq, compacted)``, no sort.

    One boolean scatter over the id range plus a lookup-table gather —
    O(m + extent) where ``np.unique`` would sort in O(m log m); the extent
    term is a byte per id, trivial even at millions of ranks.  ``uniq`` is
    ascending and ``uniq[compacted] == col`` elementwise, so codes built
    from the compacted ids stay monotone in the original ids and dedup
    results translate back by a gather without re-sorting.
    """
    mask = np.zeros(int(col.max()) + 1, bool)
    mask[col] = True
    uniq = np.flatnonzero(mask)
    lut = np.zeros(len(mask), np.int64)
    lut[uniq] = np.arange(len(uniq), dtype=np.int64)
    return uniq, lut[col]


def _compact_pairs(rows: np.ndarray, peers: np.ndarray) -> tuple:
    """Id-space sketch of both pair columns: unique ids + compacted cols."""
    urows, rows_c = _compact_ids(rows)
    upeers, peers_c = _compact_ids(peers)
    return urows, rows_c, upeers, peers_c


def _pair_counts_numpy(
    group_ids: np.ndarray,
    rows: np.ndarray,
    peers: np.ndarray,
    n_groups: int,
    rank_extent: int,
    strategy: Optional[tuple] = None,
) -> np.ndarray:
    """|distinct peers| per (group, rank) over encoded pairs (NumPy).

    ``group_ids`` must be non-decreasing (the profiler's unique
    (region, struct) combinations are emitted group-major), which lets the
    chunked path slice pair runs per group with one ``searchsorted``.
    ``strategy`` forces a :func:`_dedup_strategy` decision (tests only).
    """
    m = len(rows)
    counts = np.zeros(n_groups * rank_extent, np.int64)
    if m == 0 or rank_extent == 0 or n_groups == 0:
        return counts.reshape(n_groups, rank_extent)
    stride = np.int64(int(peers.max()) + 1)
    if strategy is None:
        strategy = _dedup_strategy(n_groups, rank_extent, int(stride), m)
    kind, chunk = strategy
    if kind == "hybrid":
        urows, rows_c, upeers, peers_c = _compact_pairs(rows, peers)
        sub = _dedup_strategy(n_groups, len(urows), len(upeers), m)
        if sub[0] == "hybrid":  # compaction exhausted — sort the small codes
            sub = ("unique", 0)
        compact = _pair_counts_numpy(
            group_ids, rows_c, peers_c, n_groups, len(urows), strategy=sub
        )
        counts = np.zeros((n_groups, rank_extent), np.int64)
        counts[:, urows] = compact
        return counts
    if kind == "unique":
        codes = (group_ids * rank_extent + rows) * stride + peers
        uniq = np.unique(codes)
        counts = np.bincount(uniq // stride, minlength=n_groups * rank_extent)
    elif kind == "bitmap":
        codes = (group_ids * rank_extent + rows) * stride + peers
        bitmap = np.zeros(n_groups * rank_extent * int(stride), bool)
        bitmap[codes] = True
        counts = bitmap.reshape(n_groups * rank_extent, int(stride)).sum(axis=1)
    else:  # chunked: dense scatter per run of groups, bounded peak memory
        bounds = np.searchsorted(group_ids, np.arange(n_groups + 1))
        for g0 in range(0, n_groups, chunk):
            g1 = min(g0 + chunk, n_groups)
            lo, hi = int(bounds[g0]), int(bounds[g1])
            if lo == hi:
                continue
            local = (
                (group_ids[lo:hi] - g0) * rank_extent + rows[lo:hi]
            ) * stride + peers[lo:hi]
            bitmap = np.zeros((g1 - g0) * rank_extent * int(stride), bool)
            bitmap[local] = True
            counts[g0 * rank_extent : g1 * rank_extent] = bitmap.reshape(
                (g1 - g0) * rank_extent, int(stride)
            ).sum(axis=1)
    return counts.reshape(n_groups, rank_extent).astype(np.int64, copy=False)


#: Bit position of the rank in a fixed ``(rank << 32) | peer`` pair code.
PAIR_CODE_SHIFT = 32


def _decode_pair_codes(
    uniq: np.ndarray, n_groups: int, rank_extent: int, stride: int
) -> tuple:
    """Split sorted unique compound codes into per-group fixed pair codes.

    ``uniq`` holds sorted ``(group * rank_extent + rank) * stride + peer``
    codes.  The compound encoding is monotone in (group, rank, peer) and
    the fixed ``(rank << PAIR_CODE_SHIFT) | peer`` encoding is monotone in
    (rank, peer), so within each group the converted codes stay sorted —
    no re-sort needed.  Returns ``(indptr, codes)`` CSR over groups.
    """
    per_group = np.int64(rank_extent) * np.int64(stride)
    g = uniq // per_group
    local = uniq - g * per_group
    codes = ((local // stride) << PAIR_CODE_SHIFT) | (local % stride)
    indptr = np.searchsorted(g, np.arange(n_groups + 1)).astype(np.int64)
    return indptr, codes.astype(np.int64, copy=False)


def _pair_codes_numpy(
    group_ids: np.ndarray,
    rows: np.ndarray,
    peers: np.ndarray,
    n_groups: int,
    strategy: Optional[tuple] = None,
) -> tuple:
    """Distinct (rank, peer) sets per group as sorted unique fixed codes.

    The mergeable twin of :func:`_pair_counts_numpy`: same non-decreasing
    ``group_ids`` contract, same :func:`_dedup_strategy` split (dense
    bitmap / chunked bitmap / sort-based unique), but instead of
    collapsing to per-rank counts it returns ``(indptr, codes)`` — a CSR
    over groups of sorted unique ``(rank << PAIR_CODE_SHIFT) | peer``
    int64 codes.  The encoding is *fixed* (no data-dependent stride), so
    code sets from different deltas/shards union directly
    (:mod:`repro.core.streaming` merges them with ``np.union1d``).
    """
    m = len(rows)
    if m == 0 or n_groups == 0:
        return np.zeros(n_groups + 1, np.int64), np.zeros(0, np.int64)
    rank_extent = int(rows.max()) + 1
    stride = int(peers.max()) + 1
    if rank_extent > (1 << 31) or stride > (1 << PAIR_CODE_SHIFT):
        raise ValueError(
            f"rank/peer ids ({rank_extent}, {stride}) exceed the fixed "
            f"pair-code encoding"
        )
    if strategy is None:
        strategy = _dedup_strategy(n_groups, rank_extent, stride, m)
    kind, chunk = strategy
    if kind == "hybrid":
        urows, rows_c, upeers, peers_c = _compact_pairs(rows, peers)
        sub = _dedup_strategy(n_groups, len(urows), len(upeers), m)
        if sub[0] == "hybrid":  # compaction exhausted — sort the small codes
            sub = ("unique", 0)
        indptr, codes_c = _pair_codes_numpy(
            group_ids, rows_c, peers_c, n_groups, strategy=sub
        )
        # Gather through the sorted id tables: monotone in (rank, peer), so
        # per-group code order survives the translation un-sorted.
        codes = (urows[codes_c >> PAIR_CODE_SHIFT] << PAIR_CODE_SHIFT) | (
            upeers[codes_c & _PAIR_CODE_MASK]
        )
        return indptr, codes
    if kind == "unique":
        comp = (group_ids * rank_extent + rows) * stride + peers
        uniq = np.unique(comp)
    elif kind == "bitmap":
        comp = (group_ids * rank_extent + rows) * stride + peers
        bitmap = np.zeros(n_groups * rank_extent * stride, bool)
        bitmap[comp] = True
        uniq = np.flatnonzero(bitmap)
    else:  # chunked: dense scatter per run of groups, bounded peak memory
        bounds = np.searchsorted(group_ids, np.arange(n_groups + 1))
        parts = []
        base = np.int64(rank_extent) * np.int64(stride)
        for g0 in range(0, n_groups, chunk):
            g1 = min(g0 + chunk, n_groups)
            lo, hi = int(bounds[g0]), int(bounds[g1])
            if lo == hi:
                continue
            local = (
                (group_ids[lo:hi] - g0) * rank_extent + rows[lo:hi]
            ) * stride + peers[lo:hi]
            bitmap = np.zeros((g1 - g0) * rank_extent * stride, bool)
            bitmap[local] = True
            parts.append(np.flatnonzero(bitmap) + g0 * base)
        uniq = (
            np.concatenate(parts) if parts else np.zeros(0, np.int64)
        )  # chunks are group-major, so the concatenation is already sorted
    return _decode_pair_codes(uniq, n_groups, rank_extent, stride)


# ---------------------------------------------------------------------------
# Backend interface + NumPy reference
# ---------------------------------------------------------------------------


def _op_span(fn):
    """Run a backend op inside a span named after it."""
    name = fn.__name__

    @functools.wraps(fn)
    def op(*args, **kwargs):
        with span(name):
            return fn(*args, **kwargs)

    return op


class ReduceBackend:
    """Interface every reduction backend implements (NumPy in, NumPy out).
    Each op of a backend runs inside a span named after it."""

    name = "abstract"

    def matmul(self, w: np.ndarray, grid: np.ndarray) -> np.ndarray:
        """Exact int64 (G, S) @ (S, R) — never rounded."""
        raise NotImplementedError

    def block_reduce(self, grid, starts, ends, ufunc: np.ufunc) -> np.ndarray:
        raise NotImplementedError

    def segment_reduce(self, col, order, starts, ufunc: np.ufunc = np.add):
        raise NotImplementedError

    def factorize(self, col: np.ndarray) -> tuple:
        """``(uniq, first_index, inverse)`` with np.unique semantics."""
        raise NotImplementedError

    def pair_counts(self, group_ids, rows, peers, n_groups, rank_extent):
        """|distinct peers| per (group, rank); group_ids non-decreasing."""
        raise NotImplementedError

    def pair_codes(self, group_ids, rows, peers, n_groups) -> tuple:
        """Distinct (rank, peer) sets per group as sorted unique fixed
        ``(rank << PAIR_CODE_SHIFT) | peer`` codes — ``(indptr, codes)``
        CSR over groups; group_ids non-decreasing.  The mergeable form of
        :meth:`pair_counts` (see :mod:`repro.core.streaming`)."""
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return f"<{type(self).__name__} name={self.name!r}>"


class NumpyBackend(ReduceBackend):
    """The reference backend: plain NumPy, bit-exact by construction."""

    name = "numpy"

    @_op_span
    def matmul(self, w: np.ndarray, grid: np.ndarray) -> np.ndarray:
        return w @ grid

    @_op_span
    def block_reduce(self, grid, starts, ends, ufunc: np.ufunc) -> np.ndarray:
        return block_reduce(grid, starts, ends, ufunc)

    @_op_span
    def segment_reduce(self, col, order, starts, ufunc: np.ufunc = np.add):
        return segment_reduce(col, order, starts, ufunc)

    @_op_span
    def factorize(self, col: np.ndarray) -> tuple:
        uniq, first, inv = np.unique(col, return_index=True, return_inverse=True)
        return uniq, first.astype(np.int64), inv.reshape(-1).astype(np.int64)

    @_op_span
    def pair_counts(self, group_ids, rows, peers, n_groups, rank_extent):
        return _pair_counts_numpy(group_ids, rows, peers, n_groups, rank_extent)

    @_op_span
    def pair_codes(self, group_ids, rows, peers, n_groups) -> tuple:
        return _pair_codes_numpy(group_ids, rows, peers, n_groups)


# ---------------------------------------------------------------------------
# jax backend: exact int8-limb matmuls + the Pallas segmented reduce
# ---------------------------------------------------------------------------


class BackendUnavailable(RuntimeError):
    """Raised when the jax backend cannot run here (no jax, or no x64)."""


def _import_jax():
    """Deferred jax import (monkeypatched by the unavailability tests)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compat import enable_x64

    return jax, jnp, enable_x64


def _x64_ok() -> bool:
    """True when the x64 scope actually yields 64-bit array types."""
    _, jnp, enable_x64 = _import_jax()
    with enable_x64():
        return bool(jnp.zeros((), jnp.int64).dtype == np.dtype(np.int64))


#: Matmul limbs are 7 bits wide (the top limb carries the sign), so every
#: limb is an int8 and one int8 x int8 product is at most 2**14 in size.
_LIMB_BITS = 7

#: int32 accumulation of int8 products stays exact over this many terms
#: (2**17 - 1 products of magnitude <= 2**14 sum below 2**31); longer
#: contractions split into chunks summed on the host.
_LIMB_DOT_MAX_K = (1 << 17) - 1


def _n_limbs(arr: np.ndarray) -> int:
    """Fewest signed 7-bit limbs that hold every value: the top limb
    ``v >> 7 * (k - 1)`` must lie in the int8 range."""
    lo, hi = int(arr.min()), int(arr.max())
    k = 1
    while not (-(1 << (_LIMB_BITS * k)) <= lo and hi < (1 << (_LIMB_BITS * k))):
        k += 1
    return k


def _limbs(arr: np.ndarray, k: int) -> np.ndarray:
    """``(k, *arr.shape)`` int8 limbs with ``arr == sum(limb_i << 7 i)``:
    limbs below the top are in ``[0, 127]``, the top one is signed."""
    out = [((arr >> (_LIMB_BITS * i)) & 127) for i in range(k - 1)]
    out.append(arr >> (_LIMB_BITS * (k - 1)))
    return np.stack(out).astype(np.int8)


@functools.lru_cache(maxsize=None)
def _limb_dot_fn():
    """jit-compiled int8 x int8 -> int32 dot (exact on every backend)."""
    jax, jnp, _ = _import_jax()

    def dot(a, b):
        with jax.named_scope("repro.limb_dot"):
            return jax.lax.dot(a, b, preferred_element_type=jnp.int32)

    return jax.jit(dot)


def _limb_matmul(w: np.ndarray, grid: np.ndarray) -> np.ndarray:
    """Exact int64 ``w @ grid`` as one device dot over stacked int8 limbs.

    ``(ka*G, S) @ (S, kb*R)`` gives every limb-pair product in int32; the
    host recombines them with shifts in uint64, i.e. modulo 2**64 — the
    same wrap-around NumPy's int64 matmul has, so results are identical
    for every int64 input, negative values included.
    """
    g, s = w.shape
    r = grid.shape[1]
    ka, kb = _n_limbs(w), _n_limbs(grid)
    a = _limbs(w, ka).reshape(ka * g, s)
    b = np.moveaxis(_limbs(grid, kb), 0, 1).reshape(s, kb * r)
    acc = np.zeros((g, r), np.uint64)
    dot = _limb_dot_fn()
    for lo in range(0, s, _LIMB_DOT_MAX_K):
        hi = min(s, lo + _LIMB_DOT_MAX_K)
        with span("device_roundtrip"):
            part = np.asarray(dot(a[:, lo:hi], b[lo:hi]))
        part = part.astype(np.int64)
        part = part.reshape(ka, g, kb, r).astype(np.uint64)
        for i in range(ka):
            for j in range(kb):
                acc += part[i, :, j] << np.uint64(_LIMB_BITS * (i + j))
    return acc.view(np.int64)


_SEG_OPS = {np.add: "sum", np.maximum: "max", np.minimum: "min"}

#: Tiles of the Pallas segmented reduce.  Rows are the one-hot contraction
#: axis and ride the 128-lane axis of the segment-id block; segment and
#: column tiles follow the (8, 128) int32 tiling.  The column (rank) axis
#: is a grid axis, so VMEM use is fixed whatever the rank count.
_SEG_ROWS = 256
_SEG_BLOCK = 128
_SEG_COLS = 512

#: The sum path splits values into 8-bit limbs: exact in bf16, and a
#: one-hot bf16 matmul over one row tile sums them exactly in f32
#: (255 * 256 < 2**24).  The int32 accumulator then holds up to this many
#: rows per segment exactly.
_SUM_LIMB_BITS = 8
_SUM_MAX_ROWS = ((1 << 31) - 1) // 255


def _seg_layout(seg: np.ndarray, n_segments: int) -> dict:
    """Row layout that gives every row tile exactly one segment block.

    Rows are sorted by segment, so each block of ``_SEG_BLOCK`` segments
    owns a contiguous row range; padding each range to whole row tiles
    (at least one) lets the kernel map row tile -> output segment block
    through a scalar-prefetched table.  Padding rows carry segment -1 and
    match nothing.
    """
    n = len(seg)
    n_sb = -(-n_segments // _SEG_BLOCK)
    bounds = np.searchsorted(seg, np.arange(n_sb + 1) * _SEG_BLOCK)
    n_rb = np.maximum(1, -(-np.diff(bounds) // _SEG_ROWS))
    rb_off = np.concatenate(([0], np.cumsum(n_rb)))
    sb_of_row = seg // _SEG_BLOCK
    dest = rb_off[sb_of_row] * _SEG_ROWS + np.arange(n) - bounds[sb_of_row]
    total = int(rb_off[-1])
    sids = np.full(total * _SEG_ROWS, -1, np.int32)
    sids[dest] = seg
    sb_of_rb = np.repeat(np.arange(n_sb), n_rb)
    first = np.zeros(total, np.int32)
    first[rb_off[:-1]] = 1
    # local segment range of each row tile (max/min loop bounds)
    tiles = sids.reshape(total, _SEG_ROWS)
    valid = tiles >= 0
    base = (sb_of_rb * _SEG_BLOCK)[:, None]
    lo = np.where(valid, tiles - base, _SEG_BLOCK).min(axis=1)
    hi = np.where(valid, tiles - base, -1).max(axis=1)
    return dict(
        dest=dest,
        n_rows=total * _SEG_ROWS,
        n_sb=n_sb,
        sids=sids,
        sb_of_rb=sb_of_rb.astype(np.int32),
        first=first,
        lo=lo.astype(np.int32),
        hi=hi.astype(np.int32),
    )


@functools.lru_cache(maxsize=None)
def _seg_kernel(op: str, k: int, n_rows: int, n_sb: int, c_pad: int, interpret: bool):
    """jit-compiled segmented reduce over int32 tiles (cached per shape).

    ``sum``: grid (limb, column tile, row tile); each row tile adds the
    one-hot ``(segments x rows) @ (rows x columns)`` product of its 8-bit
    limbs into its segment block's int32 output tile on the MXU.
    ``max``/``min``: grid (column tile, row tile); each row tile folds a
    masked column reduction per segment it holds into one output row.
    Row tiles run in order, and a segment block's tiles are consecutive,
    so its output tile stays resident until the block is done.
    """
    jax, jnp, _ = _import_jax()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, S = _SEG_ROWS, _SEG_BLOCK
    C = min(_SEG_COLS, c_pad)
    n_rb, n_cb = n_rows // R, c_pad // C

    if op == "sum":

        def kernel(sb_ref, first_ref, sid_ref, val_ref, out_ref):
            rb = pl.program_id(2)

            @pl.when(first_ref[rb] == 1)
            def _init():
                out_ref[...] = jnp.zeros(out_ref.shape, jnp.int32)

            seg = sb_ref[rb] * S + jax.lax.broadcasted_iota(jnp.int32, (S, R), 0)
            onehot = (sid_ref[...] == seg).astype(jnp.bfloat16)
            part = jnp.dot(
                onehot,
                val_ref[...].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
            out_ref[...] += part.astype(jnp.int32)

        call = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(k, n_cb, n_rb),
                in_specs=[
                    pl.BlockSpec((1, R), lambda j, c, r, sb, fi: (0, r)),
                    pl.BlockSpec((None, R, C), lambda j, c, r, sb, fi: (j, r, c)),
                ],
                out_specs=pl.BlockSpec(
                    (None, S, C), lambda j, c, r, sb, fi: (j, sb[r], c)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct((k, n_sb * S, c_pad), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
            name="repro_segment_sum",
        )

        def run(sb_of_rb, first, lo, hi, sids, vals):
            return call(sb_of_rb, first, sids.reshape(1, n_rows), vals)

    else:
        info = np.iinfo(np.int32)
        init = int(info.min if op == "max" else info.max)
        fold = jnp.maximum if op == "max" else jnp.minimum
        red = jnp.max if op == "max" else jnp.min

        def kernel(sb_ref, first_ref, lo_ref, hi_ref, sid_ref, val_ref, out_ref):
            rb = pl.program_id(1)

            @pl.when(first_ref[rb] == 1)
            def _init():
                out_ref[...] = jnp.full(out_ref.shape, init, jnp.int32)

            base = sb_ref[rb] * S
            sids = sid_ref[...]
            vals = val_ref[...]

            def body(s, carry):
                hit = sids == base + s
                row = red(jnp.where(hit, vals, init), axis=0, keepdims=True)
                out_ref[pl.ds(s, 1), :] = fold(out_ref[pl.ds(s, 1), :], row)
                return carry

            jax.lax.fori_loop(lo_ref[rb], hi_ref[rb] + 1, body, 0)

        idx = lambda c, r, sb, fi, lo, hi: (r, 0)  # noqa: E731
        call = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4,
                grid=(n_cb, n_rb),
                in_specs=[
                    pl.BlockSpec((R, 1), idx),
                    pl.BlockSpec((R, C), lambda c, r, sb, fi, lo, hi: (r, c)),
                ],
                out_specs=pl.BlockSpec(
                    (S, C), lambda c, r, sb, fi, lo, hi: (sb[r], c)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct((n_sb * S, c_pad), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
            name=f"repro_segment_{op}",
        )

        def run(sb_of_rb, first, lo, hi, sids, vals):
            return call(sb_of_rb, first, lo, hi, sids.reshape(n_rows, 1), vals)

    return jax.jit(run)


def _pallas_segment_reduce(
    vals: np.ndarray,
    seg: np.ndarray,
    n_segments: int,
    op: str,
    *,
    interpret: bool,
) -> Optional[np.ndarray]:
    """Exact segmented reduce of int64 ``vals (N, C)`` on the Pallas kernel.

    Only 32-bit values reach the kernel; x64 stays on the host side:

    * ``sum`` shifts values by their minimum, splits them into 8-bit limbs
      and sums limbs exactly (see :data:`_SUM_LIMB_BITS`); the host
      recombines limbs and the shift in uint64, i.e. modulo 2**64 like
      NumPy's int64 sum.
    * ``max``/``min`` shift values into int32 when their range is below
      2**32 (an order-preserving map) and shift the result back.

    Returns None when 32 bits cannot hold the operation exactly — a value
    range of 2**32 or more (max/min), or more than
    :data:`_SUM_MAX_ROWS` rows in one segment (sum); the caller then runs
    XLA's exact ``segment_*`` under x64.  ``seg`` holds the sorted
    segment id of every row.
    """
    n, c = vals.shape
    vmin, vmax = int(vals.min()), int(vals.max())
    counts = np.bincount(seg, minlength=n_segments)
    if op == "sum":
        if int(counts.max()) > _SUM_MAX_ROWS:
            return None
        u = vals.view(np.uint64) - np.uint64(vmin % (1 << 64))
        k = max(1, -(-int(u.max()).bit_length() // _SUM_LIMB_BITS))
        bits = [np.uint64(_SUM_LIMB_BITS * j) for j in range(k)]
        host = np.stack([(u >> b) & np.uint64(255) for b in bits]).astype(np.int32)
    else:
        if vmax - vmin >= (1 << 32):
            return None
        shift = vmin + (1 << 31)
        host = (vals - shift).astype(np.int32)[None]
        k = 1
    lay = _seg_layout(seg, n_segments)
    c_pad = -(-c // 128) * 128
    if c_pad > _SEG_COLS:
        c_pad = -(-c_pad // _SEG_COLS) * _SEG_COLS
    padded = np.zeros((k, lay["n_rows"], c_pad), np.int32)
    padded[:, lay["dest"], :c] = host
    fn = _seg_kernel(op, k, lay["n_rows"], lay["n_sb"], c_pad, interpret)
    with span("device_roundtrip"):
        out = np.asarray(
            fn(
                lay["sb_of_rb"],
                lay["first"],
                lay["lo"],
                lay["hi"],
                lay["sids"],
                padded if op == "sum" else padded[0],
            )
        )
    if op == "sum":
        acc = (counts.astype(np.uint64) * np.uint64(vmin % (1 << 64)))[:, None]
        acc = np.broadcast_to(acc, (n_segments, c)).copy()
        for j in range(k):
            part = out[j, :n_segments, :c].astype(np.uint64)
            acc += part << np.uint64(_SUM_LIMB_BITS * j)
        return acc.view(np.int64)
    return out[:n_segments, :c].astype(np.int64) + shift


class JaxBackend(ReduceBackend):
    """jax reductions on the default device; x64 is scoped to the calls
    that need it, never enabled process-wide.

    On a TPU the segmented reductions always run the compiled Pallas
    kernel.  Elsewhere they run XLA's ``segment_*`` ops, unless
    ``interpret=True`` asks for the Pallas kernel in interpret mode — the
    way CPU tests check the kernel.  Interpret mode on a TPU is refused.
    Construction raises :class:`BackendUnavailable` when jax is missing
    or x64 cannot be enabled.

    Each call an op makes to the device, from its NumPy inputs to its
    NumPy output, runs inside a ``device_roundtrip`` span: the puts, the
    dispatch, the device's work, the wait for it and the read back.
    """

    name = "jax"

    def __init__(self, interpret: bool = False):
        try:
            self._jax, self._jnp, self._enable_x64 = _import_jax()
        except ImportError as e:
            raise BackendUnavailable(f"jax is not importable: {e!r}") from e
        if not _x64_ok():
            raise BackendUnavailable(
                "jax x64 mode is unavailable; exact int64 reductions need it"
            )
        self.platform = self._jax.default_backend()
        if interpret and self.platform == "tpu":
            raise ValueError("Pallas interpret mode is for CPU tests, not the TPU")
        self.interpret = bool(interpret)
        self.use_pallas = self.platform == "tpu" or self.interpret

    # -- exact int64 matmul -------------------------------------------------
    @_op_span
    def matmul(self, w: np.ndarray, grid: np.ndarray) -> np.ndarray:
        w = np.ascontiguousarray(w, np.int64)
        grid = np.ascontiguousarray(grid, np.int64)
        g, s = w.shape
        r = grid.shape[1]
        if g == 0 or s == 0 or r == 0:
            return np.zeros((g, r), np.int64)
        return _limb_matmul(w, grid)

    # -- segmented reductions -----------------------------------------------
    def _segment_apply(self, vals: np.ndarray, seg: np.ndarray, nseg: int, op):
        if self.use_pallas and np.issubdtype(vals.dtype, np.integer):
            flat = vals if vals.ndim == 2 else vals[:, None]
            out = _pallas_segment_reduce(
                flat.astype(np.int64), seg, nseg, op, interpret=self.interpret
            )
            if out is not None:
                return out if vals.ndim == 2 else out[:, 0]
        jax = self._jax
        fns = {
            "sum": jax.ops.segment_sum,
            "max": jax.ops.segment_max,
            "min": jax.ops.segment_min,
        }
        with self._enable_x64(), span("device_roundtrip"):
            out = fns[op](
                vals,
                seg,
                num_segments=nseg,
                indices_are_sorted=True,
            )
            return np.asarray(out)

    @_op_span
    def block_reduce(self, grid, starts, ends, ufunc: np.ufunc) -> np.ndarray:
        op = _SEG_OPS.get(ufunc)
        if op is None or getattr(grid, "ndim", 0) != 2:
            return block_reduce(grid, starts, ends, ufunc)
        nseg = len(starts)
        if nseg == 0:
            return np.zeros((0,) + grid.shape[1:], grid.dtype)
        lens = np.asarray(ends) - np.asarray(starts)
        n = int(lens.sum())
        offs = np.zeros(nseg, np.int64)
        np.cumsum(lens[:-1], out=offs[1:])
        idx = np.repeat(starts, lens) + (np.arange(n) - np.repeat(offs, lens))
        seg = np.repeat(np.arange(nseg, dtype=np.int64), lens)
        out = self._segment_apply(grid[idx], seg, nseg, op)
        return out.astype(grid.dtype, copy=False)

    @_op_span
    def segment_reduce(self, col, order, starts, ufunc: np.ufunc = np.add):
        if not len(starts):
            return np.zeros(0, col.dtype)
        op = _SEG_OPS.get(ufunc)
        if op is None:
            return segment_reduce(col, order, starts, ufunc)
        vals = col if order is None else col[order]
        seg = _segment_ids(np.asarray(starts), len(vals))
        out = self._segment_apply(np.asarray(vals), seg, len(starts), op)
        return out.astype(col.dtype, copy=False)

    # -- factorize / dedup ----------------------------------------------------
    @_op_span
    def factorize(self, col: np.ndarray) -> tuple:
        col = np.asarray(col)
        with self._enable_x64(), span("device_roundtrip"):
            uniq, inv = self._jnp.unique(col, return_inverse=True)
            uniq = np.asarray(uniq)
            inv = np.asarray(inv)
        inv = inv.reshape(-1).astype(np.int64)
        # first-occurrence indices derived from the inverse (np.unique's
        # return_index contract), independent of jnp.unique tie-breaking
        first = np.full(len(uniq), len(inv), np.int64)
        np.minimum.at(first, inv, np.arange(len(inv), dtype=np.int64))
        return uniq, first, inv

    @_op_span
    def pair_counts(self, group_ids, rows, peers, n_groups, rank_extent):
        m = len(rows)
        if m == 0 or rank_extent == 0 or n_groups == 0:
            return np.zeros((n_groups, rank_extent), np.int64)
        if rank_extent > _SKETCH_RANK_EXTENT:
            # Host-side sketch/chunked hybrid: at this extent the id
            # compaction + dense scatter beats a device sort of the raw
            # codes (and is bit-identical by the backend contract).
            return _pair_counts_numpy(
                group_ids, rows, peers, n_groups, rank_extent, strategy=("hybrid", 0)
            )
        stride = np.int64(int(peers.max()) + 1)
        codes = (group_ids * rank_extent + rows) * stride + peers
        with self._enable_x64(), span("device_roundtrip"):
            uniq = np.asarray(self._jnp.unique(codes))
        counts = np.bincount(uniq // stride, minlength=n_groups * rank_extent)
        return counts.reshape(n_groups, rank_extent).astype(np.int64, copy=False)

    @_op_span
    def pair_codes(self, group_ids, rows, peers, n_groups) -> tuple:
        m = len(rows)
        if m == 0 or n_groups == 0:
            return np.zeros(n_groups + 1, np.int64), np.zeros(0, np.int64)
        rank_extent = int(rows.max()) + 1
        stride = int(peers.max()) + 1
        if rank_extent > (1 << 31) or stride > (1 << PAIR_CODE_SHIFT):
            raise ValueError(
                f"rank/peer ids ({rank_extent}, {stride}) exceed the fixed "
                f"pair-code encoding"
            )
        if rank_extent > _SKETCH_RANK_EXTENT:
            return _pair_codes_numpy(
                group_ids, rows, peers, n_groups, strategy=("hybrid", 0)
            )
        comp = (group_ids * rank_extent + rows) * stride + peers
        with self._enable_x64(), span("device_roundtrip"):
            uniq = np.asarray(self._jnp.unique(comp))
        return _decode_pair_codes(uniq, n_groups, rank_extent, stride)


# ---------------------------------------------------------------------------
# Selection: explicit arg > use_backend() override > REPRO_BACKEND > numpy
# ---------------------------------------------------------------------------

_instances: dict = {}
_instances_lock = threading.Lock()
_tls = threading.local()


def available_backends() -> tuple:
    return ("numpy", "jax")


def _instance(name: str) -> ReduceBackend:
    with _instances_lock:
        inst = _instances.get(name)
        if inst is None:
            inst = NumpyBackend() if name == "numpy" else JaxBackend()
            _instances[name] = inst
        return inst


def resolve_backend(
    backend: Union[ReduceBackend, str, None] = None,
) -> ReduceBackend:
    """Resolve a backend name/instance to a :class:`ReduceBackend`.

    Priority: explicit ``backend`` argument, then a :func:`use_backend`
    thread-local override, then the ``REPRO_BACKEND`` environment variable,
    then ``"numpy"``.  Nothing falls back: an unknown name (from any
    source) raises ``ValueError``, and ``"jax"`` raises
    :class:`BackendUnavailable` when jax is missing or x64 cannot be
    enabled.
    """
    if isinstance(backend, ReduceBackend):
        return backend
    name = backend
    if name is None:
        override = getattr(_tls, "override", None)
        if isinstance(override, ReduceBackend):
            return override
        name = override
    if name is None:
        name = os.environ.get(BACKEND_ENV)
    if name is None:
        return _instance("numpy")
    name = str(name).strip().lower()
    if name not in available_backends():
        raise ValueError(
            f"unknown reduction backend: {backend or name!r} "
            f"(expected one of {available_backends()}; {BACKEND_ENV} "
            f"is read when no backend is passed)"
        )
    return _instance(name)


@contextmanager
def use_backend(backend: Union[ReduceBackend, str, None]):
    """Thread-local default backend for the scope (sweep runners use this
    so app ``profile()`` entry points need no signature change)."""
    if isinstance(backend, str):
        if backend.strip().lower() not in available_backends():
            raise ValueError(
                f"unknown reduction backend: {backend!r} "
                f"(expected one of {available_backends()})"
            )
    prev = getattr(_tls, "override", None)
    _tls.override = backend
    try:
        yield
    finally:
        _tls.override = prev
