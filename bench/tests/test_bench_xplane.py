"""The reduction from a profiler trace to busy time, idle share, collective
time, op times and named idle gaps, on small traces kept in ``fixtures/``;
and the per-layer readers on what it gives."""

from __future__ import annotations

import os

import numpy as np
import pytest
from conftest import BENCH_DIR

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


@pytest.fixture(scope="module")
def two_chips():
    import xplane
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "two_chips.pbtxt")) as f:
        return xplane.summarize(ProfileData.from_text_proto(f.read()))


def test_merge_takes_the_union_of_intervals():
    import xplane

    s, e = xplane.merge(np.array([5.0, 0.0, 2.0, 9.0]), np.array([6.0, 3.0, 4.0, 10.0]))
    assert s.tolist() == [0.0, 5.0, 9.0] and e.tolist() == [4.0, 6.0, 10.0]
    s, e = xplane.merge(np.array([]), np.array([]))
    assert len(s) == len(e) == 0


def test_busy_and_idle_share(two_chips):
    # chip 0: [0,5] (clipped at the window's start) + [10,50] + [60,70] us;
    # chip 1: [0,90] us; an op past the window's end does not count
    assert two_chips.window_s == pytest.approx(100e-6)
    assert two_chips.busy_s == pytest.approx([55e-6, 90e-6])
    assert two_chips.mean_busy_s == pytest.approx(72.5e-6)
    assert two_chips.idle_pct == pytest.approx(27.5)


def test_collective_time_per_chip(two_chips):
    assert two_chips.collective_s == pytest.approx([10e-6, 10e-6])


def test_op_times_and_named_gaps(two_chips):
    bd = two_chips.breakdown()
    ops = dict(bd["device_ops"])
    assert ops == pytest.approx(
        {
            "fusion.1": 50e-6,
            "while.2": 15e-6,
            "collective-permute-done.3": 5e-6,
            "fusion.4": 2.5e-6,
            "all-reduce.5": 5e-6,
        }
    )
    assert [k for k, _ in bd["device_ops"]][0] == "fusion.1"
    # gaps by the innermost bench span over their midpoint, mean per chip
    assert dict(bd["idle_gaps"]) == pytest.approx(
        {"dispatch": 2.5e-6, "reduce": 5e-6, "other": 20e-6}
    )


def test_a_trace_without_the_window_is_refused():
    import xplane
    from jax.profiler import ProfileData

    with open(os.path.join(FIXTURES, "two_chips.pbtxt")) as f:
        text = f.read().replace('"bench.window"', '"bench.other"')
    with pytest.raises(ValueError, match="no bench.window"):
        xplane.summarize(ProfileData.from_text_proto(text))


def _reader(name):
    import harness

    return harness.load_module(os.path.join(BENCH_DIR, "metrics", f"{name}.py"))


def test_readers_on_the_trace(two_chips):
    import peaks

    obs = {
        "trace": two_chips,
        "peaks": peaks.peaks("TPU v5 lite"),
        "steps": 2,
        "compulsory_bytes_per_chip": 819,  # 1 ns at 819 GB/s
        "step_temp_bytes": 2.5e9,
    }
    assert _reader("device_idle.exec").read(obs) == pytest.approx(27.5)
    assert _reader("device_idle.turnaround").read(obs) == pytest.approx(27.5)
    # 1 ns over (72.5 us / 2 steps)
    assert _reader("sweep_roofline").read(obs) == pytest.approx(100 * 1e-9 / 36.25e-6)
    assert _reader("collective_ms.exec4").read(obs) == pytest.approx(1e3 * 10e-6 / 2)
    assert _reader("step_temp_gb").read(obs) == pytest.approx(2.5)


def test_span_readers_and_readers_that_find_nothing():
    obs = {"points": 4, "span_s": {"trace": 2.0, "reduce": 1.2, "frame": 0.04}}
    assert _reader("trace_ms.point").read(obs) == pytest.approx(200.0)
    assert _reader("reduce_ms.point").read(obs) == pytest.approx(300.0)
    assert _reader("frame_ms.point").read(obs) == pytest.approx(10.0)
    for name in (
        "trace_ms.point",
        "reduce_ms.point",
        "frame_ms.point",
        "sweep_roofline",
        "collective_ms.exec4",
        "device_idle.exec",
        "step_temp_gb",
    ):
        assert _reader(name).read({}) is None
