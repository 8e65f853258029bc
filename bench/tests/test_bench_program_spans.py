"""The readers of the program's own spans, on a span store of their own:
each reads its value over the last ``points`` spans called ``point``, and
nothing where the store has wrapped or holds no ``point`` span."""

from __future__ import annotations

import os

import pytest
from conftest import BENCH_DIR

from repro.core import tracing

#: Each reader and what it reads from the two points of :func:`_record`.
EXPECTED = {
    "eval_shape_ms.point": (300 + 200) / 2 / 1e6,
    "intern_ms.point": (7000 + 3000) / 2 / 1e6,
    "runner_self_ms.point": (103 + 103) / 2 / 1e6,
    "materialize_ms.point": (40 + 20) / 2 / 1e6,
    "reduce_ops_ms.point": (25 + 15 + 20 + 10) / 2 / 1e6,
    "device_roundtrip_ms.point": (15 + 10) / 2 / 1e6,
    "recorded_rows.point": (12 + 8) / 2,
    "compiles.window": 1,
}


def _reader(name):
    import harness

    return harness.load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"))


@pytest.fixture
def store(monkeypatch):
    fresh = tracing.Store(capacity=64)
    monkeypatch.setattr(tracing, "_store", fresh)
    now = [0]

    def clock():
        return now[0]

    monkeypatch.setattr(tracing, "_clock", clock)
    return fresh, now


def _record(now):
    """A warm-up point, then the window's two points."""
    sp = tracing.span

    def point(trace_ns, rows, intern_ns, mat_ns, ops, compiles=0):
        with sp("point"):
            now[0] += 100
            with sp("eval_shape"):
                tracing.count("rows", rows)
                tracing.count("intern_ns", intern_ns)
                tracing.count("compiles", compiles)
                now[0] += trace_ns
            with sp("reduce"):
                with sp("materialize"):
                    now[0] += mat_ns
                for op, own, device in ops:
                    with sp(op):
                        now[0] += own
                        with sp("device_roundtrip"):
                            now[0] += device
                now[0] += 5
            now[0] += 3  # the roofline stamp: the point's own time

    point(999, 99, 999, 999, [("matmul", 999, 999)], compiles=4)
    point(300, 12, 7000, 40, [("matmul", 25, 15)], compiles=1)
    point(200, 8, 3000, 20, [("pair_counts", 20, 10)])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_window_points(store, name):
    _, now = store
    _record(now)
    got = _reader(name).read({"points": 2})
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_without_point_spans(store, name):
    _, now = store
    with tracing.span("reduce"):
        now[0] += 10
    assert _reader(name).read({"points": 2}) is None
    assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_nothing_from_a_wrapped_store(monkeypatch, store, name):
    _, now = store
    monkeypatch.setattr(tracing, "_store", tracing.Store(capacity=10))
    _record(now)  # 18 spans: the window's first point is overwritten
    assert _reader(name).read({"points": 2}) is None
    assert _reader(name).read({"points": 1}) is not None
