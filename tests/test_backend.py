"""Backend selection, exactness, and dedup-strategy unit tests.

Covers the :mod:`repro.core.backend` substrate on its own terms:
``REPRO_BACKEND`` env parsing, the errors raised when jax is missing or x64
is off or a name is unknown (never a silent NumPy fallback), dispatch from
``CommPatternProfiler`` / ``Frame.agg`` into the selected backend, the
exact-int64 matmul over int8 limbs (negative inputs included), the Pallas
segmented reduce in interpret mode, and the peer-set dedup strategy split
(dense bitmap / chunked bitmap / sort-based ``np.unique``) that replaced
the historical ``G * Rmax * stride`` single-allocation bitmap.  End-to-end
bit-identical
profile parity lives in ``test_backend_parity.py``; timing assertions in
``test_backend_perf.py``.
"""

import numpy as np
import pytest

from repro.core import backend as B
from repro.core.backend import (
    BACKEND_ENV,
    BackendUnavailable,
    JaxBackend,
    NumpyBackend,
    _dedup_strategy,
    _limbs,
    _n_limbs,
    _pair_counts_numpy,
    resolve_backend,
    segment_spans,
    use_backend,
)
from repro.core.profiler import CommPatternProfiler
from repro.core.regions import RegionEvent, RegionRecorder
from repro.core.thicket import Frame


def _small_recorder() -> RegionRecorder:
    rec = RegionRecorder()
    rec.record(
        RegionEvent.from_dicts(
            region="r",
            region_path=("r",),
            kind="ppermute",
            sends_per_rank={0: 1, 1: 2},
            recvs_per_rank={0: 2, 1: 1},
            dest_ranks={0: {1}, 1: {0}},
            src_ranks={0: {1}, 1: {0}},
            bytes_sent={0: 64, 1: 128},
            bytes_recv={0: 128, 1: 64},
        )
    )
    return rec


def _frame() -> Frame:
    return Frame([{"k": i % 3, "v": float(i)} for i in range(12)])


# ---------------------------------------------------------------------------
# Selection: env parsing, explicit args, use_backend override
# ---------------------------------------------------------------------------


def test_resolve_default_is_numpy(monkeypatch):
    monkeypatch.delenv(BACKEND_ENV, raising=False)
    assert isinstance(resolve_backend(), NumpyBackend)
    assert isinstance(resolve_backend(None), NumpyBackend)


def test_resolve_env_selects_jax(monkeypatch):
    monkeypatch.setenv(BACKEND_ENV, "jax")
    assert isinstance(resolve_backend(), JaxBackend)


def test_resolve_env_normalizes_whitespace_and_case(monkeypatch):
    monkeypatch.setenv(BACKEND_ENV, "  JAX \n")
    assert isinstance(resolve_backend(), JaxBackend)
    monkeypatch.setenv(BACKEND_ENV, " NumPy ")
    assert isinstance(resolve_backend(), NumpyBackend)


def test_resolve_unknown_env_warns_and_falls_back(monkeypatch):
    """An unknown REPRO_BACKEND value raises; nothing falls back to NumPy."""
    monkeypatch.setenv(BACKEND_ENV, "cuda")
    with pytest.raises(ValueError, match="unknown reduction backend"):
        resolve_backend()


def test_resolve_unknown_explicit_name_raises():
    with pytest.raises(ValueError, match="unknown reduction backend"):
        resolve_backend("cuda")


def test_resolve_explicit_instance_passthrough():
    inst = NumpyBackend()
    assert resolve_backend(inst) is inst


def test_explicit_arg_beats_env(monkeypatch):
    monkeypatch.setenv(BACKEND_ENV, "jax")
    assert isinstance(resolve_backend("numpy"), NumpyBackend)


def test_use_backend_override_beats_env(monkeypatch):
    monkeypatch.setenv(BACKEND_ENV, "numpy")
    with use_backend("jax"):
        assert isinstance(resolve_backend(), JaxBackend)
        # explicit argument still wins over the override
        assert isinstance(resolve_backend("numpy"), NumpyBackend)
    assert isinstance(resolve_backend(), NumpyBackend)


def test_use_backend_nests_and_restores():
    with use_backend("jax"):
        with use_backend("numpy"):
            assert isinstance(resolve_backend(), NumpyBackend)
        assert isinstance(resolve_backend(), JaxBackend)


def test_use_backend_unknown_name_raises_eagerly():
    with pytest.raises(ValueError, match="unknown reduction backend"):
        with use_backend("cuda"):
            pass  # pragma: no cover - must raise before entering


def test_use_backend_accepts_instances():
    inst = NumpyBackend()
    with use_backend(inst):
        assert resolve_backend() is inst


# ---------------------------------------------------------------------------
# No fallback: jax missing / x64 unavailable -> BackendUnavailable
# ---------------------------------------------------------------------------


def test_jax_missing_falls_back_with_warning(monkeypatch):
    def boom():
        raise ImportError("no module named jax")

    monkeypatch.setattr(B, "_import_jax", boom)
    monkeypatch.setattr(B, "_instances", {})  # bypass the cached instance
    with pytest.raises(BackendUnavailable, match="not importable"):
        resolve_backend("jax")


def test_x64_off_falls_back_with_warning(monkeypatch):
    monkeypatch.setattr(B, "_x64_ok", lambda: False)
    monkeypatch.setattr(B, "_instances", {})
    with pytest.raises(BackendUnavailable, match="x64"):
        resolve_backend("jax")


def test_jax_backend_ctor_raises_backend_unavailable(monkeypatch):
    monkeypatch.setattr(B, "_x64_ok", lambda: False)
    with pytest.raises(BackendUnavailable, match="x64"):
        JaxBackend()


def test_fallback_still_profiles(monkeypatch):
    """A profile asked of an unavailable jax backend fails loudly instead
    of quietly reducing on NumPy."""
    monkeypatch.setattr(B, "_x64_ok", lambda: False)
    monkeypatch.setattr(B, "_instances", {})
    with pytest.raises(BackendUnavailable):
        CommPatternProfiler.from_recorder(_small_recorder(), backend="jax")


# ---------------------------------------------------------------------------
# Dispatch: both backends reachable from the profiler and Frame.agg
# ---------------------------------------------------------------------------


def _spy(monkeypatch, cls, method):
    calls = []
    orig = getattr(cls, method)

    def wrapper(self, *a, **kw):
        calls.append(method)
        return orig(self, *a, **kw)

    monkeypatch.setattr(cls, method, wrapper)
    return calls


def test_profiler_dispatches_to_jax_backend(monkeypatch):
    calls = _spy(monkeypatch, JaxBackend, "matmul")
    CommPatternProfiler.from_recorder(_small_recorder(), backend="jax")
    assert calls, "jax backend matmul never reached from from_recorder"


def test_profiler_dispatches_to_numpy_backend(monkeypatch):
    calls = _spy(monkeypatch, NumpyBackend, "matmul")
    CommPatternProfiler.from_recorder(_small_recorder(), backend="numpy")
    assert calls, "numpy backend matmul never reached from from_recorder"


def test_frame_agg_dispatches_to_jax_backend(monkeypatch):
    calls = _spy(monkeypatch, JaxBackend, "factorize")
    _frame().agg(("k",), {"tot": ("v", sum)}, backend="jax")
    assert calls, "jax backend factorize never reached from Frame.agg"


def test_frame_agg_dispatches_to_numpy_backend(monkeypatch):
    calls = _spy(monkeypatch, NumpyBackend, "factorize")
    _frame().agg(("k",), {"tot": ("v", sum)}, backend="numpy")
    assert calls, "numpy backend factorize never reached from Frame.agg"


def test_env_default_reaches_profiler(monkeypatch):
    monkeypatch.setenv(BACKEND_ENV, "jax")
    calls = _spy(monkeypatch, JaxBackend, "matmul")
    CommPatternProfiler.from_recorder(_small_recorder())
    assert calls, "REPRO_BACKEND=jax never reached from_recorder"


# ---------------------------------------------------------------------------
# Exact int64 matmul (the jax backend's int8-limb dots)
# ---------------------------------------------------------------------------


def _jax_be() -> JaxBackend:
    be = resolve_backend("jax")
    assert isinstance(be, JaxBackend)
    return be


@pytest.mark.parametrize(
    "wmax,gmax",
    [
        (5, 7),  # one limb each side
        (1 << 20, 1 << 24),  # three and four limbs
        (1 << 30, 1 << 30),  # five limbs each side
        (1 << 59, 1),  # extreme single-side magnitude
    ],
)
def test_matmul_exact_vs_numpy(wmax, gmax):
    rng = np.random.default_rng(hash((wmax, gmax)) % (1 << 32))
    w = rng.integers(0, wmax + 1, size=(7, 13), dtype=np.int64)
    g = rng.integers(0, gmax + 1, size=(13, 11), dtype=np.int64)
    want = w @ g
    assert (want >= 0).all(), "test inputs must not overflow int64"
    got = _jax_be().matmul(w, g)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, want)


def test_matmul_negative_inputs_fall_back_exactly(monkeypatch):
    """Negative inputs stay on the device path (signed top limb) and
    match NumPy exactly, int64 wrap-around included."""
    calls = _spy(monkeypatch, B, "_limb_matmul")
    rng = np.random.default_rng(3)
    w = rng.integers(-50, 50, size=(4, 6), dtype=np.int64)
    g = rng.integers(-50, 50, size=(6, 5), dtype=np.int64)
    np.testing.assert_array_equal(_jax_be().matmul(w, g), w @ g)
    big = rng.integers(-(1 << 62), 1 << 62, size=(3, 9), dtype=np.int64)
    np.testing.assert_array_equal(_jax_be().matmul(big, big.T), big @ big.T)
    assert len(calls) == 2


def test_matmul_empty_shapes():
    be = _jax_be()
    a = be.matmul(np.zeros((0, 4), np.int64), np.zeros((4, 3), np.int64))
    assert a.shape == (0, 3)
    b = be.matmul(np.zeros((2, 0), np.int64), np.zeros((0, 3), np.int64))
    assert b.shape == (2, 3)


def test_limb_plan_regimes():
    """Limb counts grow 7 bits at a time; limbs are int8 and recombine
    exactly (the top limb carries the sign)."""
    assert _n_limbs(np.array([0, 127])) == 1
    assert _n_limbs(np.array([-128, 5])) == 1
    assert _n_limbs(np.array([128])) == 2
    assert _n_limbs(np.array([np.iinfo(np.int64).min])) == 9
    assert _n_limbs(np.array([np.iinfo(np.int64).max])) == 9
    rng = np.random.default_rng(4)
    v = rng.integers(-(1 << 62), 1 << 62, size=64, dtype=np.int64)
    k = _n_limbs(v)
    limbs = _limbs(v, k)
    assert limbs.dtype == np.int8 and limbs.shape == (k, 64)
    assert (limbs[:-1] >= 0).all()
    back = sum(limbs[i].astype(object) * (1 << (7 * i)) for i in range(k))
    assert [int(x) for x in back] == [int(x) for x in v]


# ---------------------------------------------------------------------------
# Peer-set dedup: strategy split + large-Rmax regression (satellite 1)
# ---------------------------------------------------------------------------


def test_dedup_strategy_small_dense_uses_bitmap():
    # plenty of pairs relative to the code space -> dense scatter
    assert _dedup_strategy(4, 64, 64, 10_000)[0] == "bitmap"


def test_dedup_strategy_sparse_uses_unique():
    # the historical failure mode: G * Rmax * stride blows past any cap
    # while only a handful of pairs exist.  cells/pair >> work factor.
    # Within the sketch extent that falls back to the sort; past it the
    # id spaces are compacted first (hybrid).
    assert _dedup_strategy(4, 50_000, 50_000, 1_000) == ("unique", 0)
    assert _dedup_strategy(4, 100_000, 100_000, 1_000) == ("hybrid", 0)


def test_dedup_strategy_large_but_dense_chunks():
    # code space over the cell cap but pairs dense enough for scatters:
    # chunk over groups, each chunk's bitmap under the cap
    g, rmax, stride = 64, 4096, 4096
    cells = g * rmax * stride  # 2**30 > _BITMAP_CELLS_CAP
    kind, chunk = _dedup_strategy(g, rmax, stride, cells // 8)
    assert kind == "chunked"
    assert 1 <= chunk < g
    assert chunk * rmax * stride <= B._BITMAP_CELLS_CAP


def test_dedup_strategy_empty_inputs():
    assert _dedup_strategy(0, 64, 64, 0) == ("unique", 0)
    assert _dedup_strategy(4, 0, 0, 0) == ("unique", 0)


def _random_pairs(rng, n_groups, rank_extent, m):
    """Encoded (group, rank, peer) pairs with group-major (sorted) groups."""
    group_ids = np.sort(rng.integers(0, n_groups, m)).astype(np.int64)
    rows = rng.integers(0, rank_extent, m).astype(np.int64)
    peers = rng.integers(0, rank_extent, m).astype(np.int64)
    return group_ids, rows, peers


@pytest.mark.parametrize(
    "forced", [("bitmap", 0), ("chunked", 3), ("chunked", 1), ("unique", 0)]
)
def test_pair_counts_strategies_identical(forced):
    rng = np.random.default_rng(11)
    group_ids, rows, peers = _random_pairs(rng, 7, 33, 4_000)
    want = _pair_counts_numpy(group_ids, rows, peers, 7, 33, strategy=("unique", 0))
    got = _pair_counts_numpy(group_ids, rows, peers, 7, 33, strategy=forced)
    np.testing.assert_array_equal(got, want)


def test_pair_counts_jax_matches_numpy():
    rng = np.random.default_rng(12)
    group_ids, rows, peers = _random_pairs(rng, 5, 41, 3_000)
    want = _pair_counts_numpy(group_ids, rows, peers, 5, 41)
    got = _jax_be().pair_counts(group_ids, rows, peers, 5, 41)
    np.testing.assert_array_equal(got, want)


def test_pair_counts_large_rmax_regression():
    """65k ranks, sparse pairs: the old dense bitmap would allocate
    G * Rmax * stride ~ 2**41 cells (terabytes); the strategy split must
    route to the sort path and still count exactly."""
    rmax = 65_536
    rng = np.random.default_rng(13)
    group_ids, rows, peers = _random_pairs(rng, 8, rmax, 20_000)
    stride = int(peers.max()) + 1
    assert _dedup_strategy(8, rmax, stride, len(rows)) == ("unique", 0)
    got = _pair_counts_numpy(group_ids, rows, peers, 8, rmax)
    want = _pair_counts_numpy(group_ids, rows, peers, 8, rmax, strategy=("unique", 0))
    np.testing.assert_array_equal(got, want)
    # spot-check one (group, rank) cell against a python set
    g0, r0 = int(group_ids[0]), int(rows[0])
    sel = (group_ids == g0) & (rows == r0)
    assert got[g0, r0] == len(set(peers[sel].tolist()))


def test_pair_counts_profile_parity_at_high_rank_counts():
    """End-to-end regression: a sparse 32k-rank trace profiles without the
    dense bitmap (strategy must not be 'bitmap') and matches the forced
    chunked scatter bit for bit."""
    rmax = 32_768
    rng = np.random.default_rng(14)
    group_ids, rows, peers = _random_pairs(rng, 4, rmax, 10_000)
    auto = _pair_counts_numpy(group_ids, rows, peers, 4, rmax)
    forced = _pair_counts_numpy(
        group_ids, rows, peers, 4, rmax, strategy=("chunked", 1)
    )
    np.testing.assert_array_equal(auto, forced)


# ---------------------------------------------------------------------------
# Hybrid (compact-then-dedup) path past the sketch rank extent
# ---------------------------------------------------------------------------


def _structured_pairs(rng, n_groups, rank_extent, m, slice_len=512):
    """Pairs whose ids occupy a thin structured slice of a huge extent —
    the shape real >= 64k-rank traces produce (halo partners cluster)."""
    group_ids = np.sort(rng.integers(0, n_groups, m)).astype(np.int64)
    base = rng.integers(0, rank_extent - slice_len)
    rows = (base + rng.integers(0, slice_len, m)).astype(np.int64)
    peers = (base + rng.integers(0, slice_len, m)).astype(np.int64)
    return group_ids, rows, peers


def test_dedup_strategy_huge_extent_routes_to_hybrid():
    rmax = B._SKETCH_RANK_EXTENT * 2
    assert _dedup_strategy(4, rmax, rmax, 50_000) == ("hybrid", 0)
    # at or below the sketch extent the sparse fallback stays sort-based
    assert _dedup_strategy(4, B._SKETCH_RANK_EXTENT, 100_000, 1_000) == ("unique", 0)


def test_compact_ids_roundtrip():
    rng = np.random.default_rng(15)
    col = rng.integers(0, 1 << 20, 5_000).astype(np.int64)
    uniq, compact = B._compact_ids(col)
    assert (np.diff(uniq) > 0).all()  # ascending, no duplicates
    np.testing.assert_array_equal(uniq[compact], col)
    assert int(compact.max()) == len(uniq) - 1


def test_pair_counts_hybrid_matches_unique():
    rng = np.random.default_rng(16)
    rmax = 200_000
    group_ids, rows, peers = _structured_pairs(rng, 6, rmax, 30_000)
    want = _pair_counts_numpy(group_ids, rows, peers, 6, rmax, strategy=("unique", 0))
    got = _pair_counts_numpy(group_ids, rows, peers, 6, rmax, strategy=("hybrid", 0))
    np.testing.assert_array_equal(got, want)
    # the auto strategy routes there on its own past the sketch extent
    stride = int(peers.max()) + 1
    assert _dedup_strategy(6, rmax, stride, len(rows)) == ("hybrid", 0)
    np.testing.assert_array_equal(
        _pair_counts_numpy(group_ids, rows, peers, 6, rmax), want
    )


def test_pair_codes_hybrid_sorted_and_identical():
    from repro.core.backend import _pair_codes_numpy

    rng = np.random.default_rng(17)
    group_ids, rows, peers = _structured_pairs(rng, 5, 150_000, 20_000)
    want_ptr, want_codes = _pair_codes_numpy(
        group_ids, rows, peers, 5, strategy=("unique", 0)
    )
    got_ptr, got_codes = _pair_codes_numpy(
        group_ids, rows, peers, 5, strategy=("hybrid", 0)
    )
    np.testing.assert_array_equal(got_ptr, want_ptr)
    np.testing.assert_array_equal(got_codes, want_codes)
    # the translated codes stay sorted within every group (merge contract)
    for g in range(5):
        seg = got_codes[got_ptr[g] : got_ptr[g + 1]]
        assert (np.diff(seg) > 0).all()


def test_jax_backend_delegates_past_sketch_extent():
    """Past _SKETCH_RANK_EXTENT the jax backend must hand dedup to the
    numpy hybrid (no device sort over a hopelessly sparse code space) and
    stay bit-identical."""
    rng = np.random.default_rng(18)
    rmax = B._SKETCH_RANK_EXTENT * 4
    group_ids, rows, peers = _structured_pairs(rng, 3, rmax, 10_000)
    be = _jax_be()
    np.testing.assert_array_equal(
        be.pair_counts(group_ids, rows, peers, 3, rmax),
        _pair_counts_numpy(group_ids, rows, peers, 3, rmax, strategy=("unique", 0)),
    )
    from repro.core.backend import _pair_codes_numpy

    want = _pair_codes_numpy(group_ids, rows, peers, 3, strategy=("unique", 0))
    got = be.pair_codes(group_ids, rows, peers, 3)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


# ---------------------------------------------------------------------------
# Pallas segmented reduce: CPU interpret-mode parity
# ---------------------------------------------------------------------------


def _pallas_be(monkeypatch) -> tuple:
    """Interpret-mode backend plus a log of kernel runs that returned a
    result (None means the op was routed to XLA's segment ops)."""
    ran = []
    orig = B._pallas_segment_reduce

    def spy(*a, **kw):
        out = orig(*a, **kw)
        ran.append(out is not None)
        return out

    monkeypatch.setattr(B, "_pallas_segment_reduce", spy)
    return JaxBackend(interpret=True), ran


@pytest.mark.parametrize("ufunc", [np.add, np.maximum, np.minimum])
def test_pallas_segment_reduce_parity(ufunc, monkeypatch):
    be, ran = _pallas_be(monkeypatch)
    rng = np.random.default_rng(21)
    key = np.sort(rng.integers(0, 9, 500)).astype(np.int64)
    # far from zero but within a 2**32 range: max/min shift into int32
    col = (1 << 40) + rng.integers(0, 1 << 31, 500).astype(np.int64)
    order, _, starts, _ = segment_spans(key)
    want = NumpyBackend().segment_reduce(col, order, starts, ufunc)
    got = be.segment_reduce(col, order, starts, ufunc)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)
    assert ran == [True], "the Pallas kernel did not produce the result"


@pytest.mark.parametrize("ufunc", [np.add, np.maximum, np.minimum])
def test_pallas_block_reduce_parity(ufunc, monkeypatch):
    be, ran = _pallas_be(monkeypatch)
    rng = np.random.default_rng(22)
    # more than one segment block, row tile and column tile
    key = np.sort(rng.integers(0, 300, 3000)).astype(np.int64)
    grid = rng.integers(-(1 << 30), 1 << 30, (3000, 700)).astype(np.int64)
    _, _, starts, ends = segment_spans(key)
    want = NumpyBackend().block_reduce(grid, starts, ends, ufunc)
    got = be.block_reduce(grid, starts, ends, ufunc)
    np.testing.assert_array_equal(got, want)
    assert ran == [True], "the Pallas kernel did not produce the result"


@pytest.mark.parametrize("ufunc", [np.add, np.maximum, np.minimum])
def test_pallas_wide_values_stay_exact(ufunc, monkeypatch):
    """Sums over the full int64 range wrap like NumPy's; max/min over a
    range 32 bits cannot hold are routed to XLA's exact segment ops."""
    be, ran = _pallas_be(monkeypatch)
    rng = np.random.default_rng(23)
    key = np.sort(rng.integers(0, 5, 400)).astype(np.int64)
    col = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max, 400)
    order, _, starts, _ = segment_spans(key)
    want = NumpyBackend().segment_reduce(col, order, starts, ufunc)
    np.testing.assert_array_equal(be.segment_reduce(col, order, starts, ufunc), want)
    assert ran == [ufunc is np.add]


def test_pallas_backend_profiles_identically(monkeypatch):
    calls = _spy(monkeypatch, JaxBackend, "matmul")
    be = JaxBackend(interpret=True)
    prof = CommPatternProfiler.from_recorder(_small_recorder(), backend=be)
    ref = CommPatternProfiler.from_recorder(_small_recorder())
    assert prof.to_json() == ref.to_json()
    assert calls, "the profile never reached the jax backend"


def test_interpret_mode_is_explicit_and_never_on_tpu(monkeypatch):
    """Off TPU the default backend runs XLA's segment ops, not Pallas;
    asking for interpret mode on a TPU is refused."""
    assert JaxBackend().use_pallas is False
    assert JaxBackend(interpret=True).use_pallas is True
    jax, _, _ = B._import_jax()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert JaxBackend().use_pallas is True
    assert JaxBackend().interpret is False
    with pytest.raises(ValueError, match="interpret"):
        JaxBackend(interpret=True)
