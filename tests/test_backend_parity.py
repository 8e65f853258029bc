"""Cross-backend bit-identity: jax reductions vs the NumPy reference.

The jax backend (XLA segment ops off TPU, and the Pallas segmented-reduce
kernel in interpret mode) must produce **byte-identical**
profiles on the real kripke/amg/laghos trace paths, on randomized event
streams (reusing ``test_profiler_parity``'s stream builder, so ragged rank
extents and sparse dicts are covered), on the golden HLO corpus, and
through every vectorized ``Frame`` reduction.  Profiles compare via
``to_json()`` — byte equality, not numeric tolerance; the int64 count/byte
paths are exact on every backend.  Every jax variant also proves, through
a dispatch spy, that it reached :class:`JaxBackend` instead of a fallback.
"""

import glob
import json
import os

import numpy as np
import pytest

from proptest import given, settings, st
from test_profiler_parity import _assert_profiles_equal, _random_recorder

from repro.apps.stencil import Decomp3D
from repro.core import backend as B
from repro.core.backend import JaxBackend, resolve_backend, use_backend
from repro.core.hlo import scan_hlo_collectives
from repro.core.profiler import CommPatternProfiler, HloCollectiveProfiler
from repro.core.thicket import Frame

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "fixtures", "hlo")
FIXTURES = sorted(glob.glob(os.path.join(FIXTURE_DIR, "*.txt")))

#: Backends that must match the NumPy reference byte for byte: the default
#: jax backend and the Pallas segmented-reduce variant, interpret-mode so
#: it runs on CPU.
JAX_VARIANTS = [
    pytest.param(lambda: "jax", id="jax"),
    pytest.param(lambda: JaxBackend(interpret=True), id="jax-pallas-interpret"),
]


@pytest.fixture
def jax_calls(monkeypatch):
    """Names of the :class:`JaxBackend` methods (and Pallas kernel runs)
    reached during the test."""
    calls = []
    for name in ("matmul", "segment_reduce", "factorize", "pair_counts"):
        orig = getattr(JaxBackend, name)

        def wrapper(self, *a, _orig=orig, _name=name, **kw):
            calls.append(_name)
            return _orig(self, *a, **kw)

        monkeypatch.setattr(JaxBackend, name, wrapper)
    kernel = B._pallas_segment_reduce

    def pallas(*a, **kw):
        out = kernel(*a, **kw)
        if out is not None:
            calls.append("pallas")
        return out

    monkeypatch.setattr(B, "_pallas_segment_reduce", pallas)
    return calls


# ---------------------------------------------------------------------------
# Randomized event streams
# ---------------------------------------------------------------------------


@given(st.integers(0, 10**6))
@settings(max_examples=25, deadline=None)
def test_random_streams_bit_identical(seed):
    rec = _random_recorder(seed)
    repl = (seed % 3) + 1
    ref = CommPatternProfiler.from_recorder(
        rec, name="p", replication=repl, backend="numpy"
    )
    assert isinstance(resolve_backend("jax"), JaxBackend)
    jx = CommPatternProfiler.from_recorder(
        rec, name="p", replication=repl, backend="jax"
    )
    _assert_profiles_equal(ref, jx)
    assert ref.to_json() == jx.to_json()


def test_random_stream_pallas_variant(jax_calls):
    rec = _random_recorder(20260808)
    ref = CommPatternProfiler.from_recorder(rec, backend="numpy")
    jx = CommPatternProfiler.from_recorder(rec, backend=JaxBackend(interpret=True))
    assert ref.to_json() == jx.to_json()
    assert "matmul" in jax_calls


# ---------------------------------------------------------------------------
# Real app trace paths (kripke / amg / laghos)
# ---------------------------------------------------------------------------


def _app_parity(profile_fn, cfg, make_backend, jax_calls):
    ref = profile_fn(cfg)
    with use_backend(make_backend()):
        jx = profile_fn(cfg)
    _assert_profiles_equal(ref, jx)
    assert ref.to_json() == jx.to_json()
    assert "matmul" in jax_calls, "the profile never reached JaxBackend"


@pytest.mark.parametrize("make_backend", JAX_VARIANTS)
def test_kripke_bit_identical(make_backend, jax_calls):
    from repro.apps.kripke import KripkeConfig, profile

    cfg = KripkeConfig(
        decomp=Decomp3D(2, 2, 2), nx=4, ny=4, nz=4, n_octants=2, fuse_messages=False
    )
    _app_parity(profile, cfg, make_backend, jax_calls)


@pytest.mark.parametrize("make_backend", JAX_VARIANTS)
def test_amg_bit_identical(make_backend, jax_calls):
    from repro.apps.amg import AMGConfig, profile

    _app_parity(profile, AMGConfig(decomp=Decomp3D(2, 2, 2)), make_backend, jax_calls)


@pytest.mark.parametrize("make_backend", JAX_VARIANTS)
def test_laghos_bit_identical(make_backend, jax_calls):
    from repro.apps.laghos import LaghosConfig, profile

    cfg = LaghosConfig(decomp=Decomp3D(2, 2, 1), nx=32, ny=32, n_steps=1)
    _app_parity(profile, cfg, make_backend, jax_calls)


@pytest.mark.parametrize("make_backend", JAX_VARIANTS)
def test_beatnik_bit_identical(make_backend, jax_calls):
    from repro.apps.beatnik import BeatnikConfig, profile

    cfg = BeatnikConfig(
        decomp=Decomp3D(2, 2, 1), nx=8, ny=8, far_subsample=8, n_steps=3
    )
    _app_parity(profile, cfg, make_backend, jax_calls)


# ---------------------------------------------------------------------------
# Golden HLO corpus
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "path", FIXTURES, ids=[os.path.basename(p)[: -len(".txt")] for p in FIXTURES]
)
@pytest.mark.parametrize("make_backend", JAX_VARIANTS)
def test_hlo_golden_bit_identical(path, make_backend, jax_calls):
    with open(path) as f:
        text = f.read()
    with open(path[: -len(".txt")] + ".expected.json") as f:
        td = json.load(f)["total_devices"]
    buf = scan_hlo_collectives(text, td, with_loops=True)
    ref = HloCollectiveProfiler.region_rows(buf, name="g", n_ranks=8, backend="numpy")
    be = make_backend()
    jx = HloCollectiveProfiler.region_rows(buf, name="g", n_ranks=8, backend=be)
    assert json.dumps(ref, sort_keys=True) == json.dumps(jx, sort_keys=True)
    if buf.n_ops:
        assert "segment_reduce" in jax_calls
        if isinstance(be, JaxBackend) and be.interpret:
            assert "pallas" in jax_calls, "the Pallas kernel never ran"


# ---------------------------------------------------------------------------
# Frame reductions
# ---------------------------------------------------------------------------


def _mixed_frame(seed):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(rng.integers(5, 60)):
        row = {
            "region": f"r{int(rng.integers(4))}",
            "rank": int(rng.integers(6)),
            "bytes": int(rng.integers(1 << 40)),
        }
        if rng.random() < 0.8:  # absent cells exercise the mask path
            row["rate"] = float(rng.random())
        rows.append(row)
    return Frame(rows)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_frame_group_by_identical(seed, jax_calls):
    f = _mixed_frame(seed)
    g_ref = f.group_by("region", "rank", backend="numpy")
    g_jax = f.group_by("region", "rank", backend="jax")
    assert list(g_ref) == list(g_jax)
    for key in g_ref:
        assert g_ref[key].rows == g_jax[key].rows
    assert "factorize" in jax_calls


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_frame_agg_identical(seed, jax_calls):
    f = _mixed_frame(seed)
    aggs = {"total": ("bytes", sum), "n": ("bytes", len)}
    ref = f.agg(("region",), aggs, backend="numpy")
    jx = f.agg(("region",), aggs, backend="jax")
    assert ref.rows == jx.rows
    assert "factorize" in jax_calls


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_frame_pivot_identical(seed, jax_calls):
    f = _mixed_frame(seed)
    ref = f.pivot("region", "rank", "bytes", backend="numpy")
    jx = f.pivot("region", "rank", "bytes", backend="jax")
    assert ref.rows == jx.rows
    assert ref.columns() == jx.columns()
    assert "factorize" in jax_calls


def test_frame_env_backend_identical(monkeypatch, jax_calls):
    f = _mixed_frame(7)
    ref = f.agg(("region",), {"total": ("bytes", sum)})
    monkeypatch.setenv("REPRO_BACKEND", "jax")
    jx = f.agg(("region",), {"total": ("bytes", sum)})
    assert ref.rows == jx.rows
    assert "factorize" in jax_calls
