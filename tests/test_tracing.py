"""The program's spans and counters (``repro.core.tracing``): the tree
arithmetic, the bounded store, compile counts, and the spans of one
spec-to-Frame point on the profiler's host plane."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tracing
from repro.core.tracing import count, span, spans


@pytest.fixture
def store(monkeypatch):
    """A small store of its own, read on a clock the test sets."""
    fresh = tracing.Store(capacity=8)
    monkeypatch.setattr(tracing, "_store", fresh)
    return fresh


@pytest.fixture
def ticks(monkeypatch):
    """``ticks(*ns)``: the clock reads these values, in order."""

    def set_(*ns):
        it = iter(ns)
        monkeypatch.setattr(tracing, "_clock", lambda: next(it))

    return set_


def test_nested_tree_gives_parents_roots_and_self_time(store, ticks):
    ticks(0, 10, 20, 30, 40, 45, 60, 70, 90, 100)
    with span("outer"):  # 0 .. 100
        with span("mid"):  # 10 .. 60
            with span("inner"):  # 20 .. 30
                pass
            with span("inner"):  # 40 .. 45
                pass
        with span("side"):  # 70 .. 90
            pass

    (outer,) = spans("outer")
    assert (outer.start_ns, outer.end_ns, outer.root) == (0, 100, outer.seq)
    n = outer.names
    assert n["outer"] == tracing.Layer(1, 100, 30, {})
    assert n["mid"] == tracing.Layer(1, 50, 35, {"outer": 1})
    assert n["inner"] == tracing.Layer(2, 15, 15, {"mid": 2})
    assert n["side"] == tracing.Layer(1, 20, 20, {"outer": 1})

    (mid,) = spans("mid")
    assert mid.root == outer.seq and set(mid.names) == {"mid", "inner"}
    assert mid.names["mid"].parents == {}  # its parent lies outside
    first, second = spans("inner", last=2)
    assert (first.start_ns, second.start_ns) == (20, 40)
    assert first.root == second.root == outer.seq
    assert first.seq < second.seq


def test_counters_land_on_the_innermost_open_span(store):
    count("rows", 5)  # no span open: dropped
    with span("outer"):
        count("rows")
        with span("inner"):
            count("rows", 2)
            count("misses")
        count("rows")
    (outer,) = spans("outer")
    (inner,) = spans("inner")
    assert inner.counters == {"rows": 2, "misses": 1}
    assert outer.counters == {"rows": 4, "misses": 1}


def test_the_store_is_bounded_and_refuses_a_wrapped_window(store):
    for _ in range(3):
        with span("a"):
            pass
    assert len(spans("a", last=3)) == 3
    with pytest.raises(LookupError) as err:
        spans("a", last=4)  # never recorded: not a wrap
    assert not isinstance(err.value, tracing.Wrapped)
    with pytest.raises(LookupError):
        spans("never")

    for _ in range(8):  # the ring holds 8: the "a" spans are overwritten
        with span("b"):
            pass
    with pytest.raises(tracing.Wrapped):
        spans("a")
    assert len(spans("b", last=8)) == 8
    with span("b"):
        pass
    assert len(spans("b", last=8)) == 8
    with pytest.raises(tracing.Wrapped):
        spans("b", last=9)  # the oldest "b" is gone: no partial window

    with span("outer"):  # its own slot is reused while it is open
        for _ in range(8):
            with span("c"):
                pass
    with pytest.raises(tracing.Wrapped):
        spans("outer")


def test_the_default_store_holds_a_window_at_ten_times_the_point_rate():
    # about 1500 points in a 51 s window, at up to 200 spans a point
    assert tracing.CAPACITY >= 1500 * 200


def test_a_compile_inside_a_span_is_counted_there():
    x = np.arange(7 * 13, dtype=np.float32).reshape(7, 13)
    with span("test_compile_here"):
        with span("test_compile_inner"):
            jax.jit(lambda a: a * 3 + 1)(x).block_until_ready()
        jnp.asarray(x)  # a transfer, no compile
    (inner,) = spans("test_compile_inner")
    assert inner.counters == {"compiles": 1}
    (outer,) = spans("test_compile_here")
    assert outer.counters["compiles"] == 1


_CACHE_LOAD = """
import sys

import jax
import numpy as np

jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
from repro.core.tracing import span, spans

loads = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _, **k: loads.append(event)
    if event == "/jax/compilation_cache/cache_retrieval_time_sec"
    else None
)
x = np.ones((5, 11), np.float32)
with span("fresh"):
    jax.jit(lambda a: a * 2 - 1)(x).block_until_ready()
jax.clear_caches()  # the next compile of the same program is a cache load
with span("load"):
    jax.jit(lambda a: a * 2 - 1)(x).block_until_ready()
print(spans("fresh")[0].counters["compiles"], spans("load")[0].counters["compiles"])
print(len(loads))
"""


def test_a_persistent_cache_load_counts_one_compile(tmp_path):
    import os
    import subprocess
    import sys

    src = os.path.dirname(os.path.dirname(os.path.dirname(tracing.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [src, env.get("PYTHONPATH")]))
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_LOAD, str(tmp_path / "cache")],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
        check=True,
    ).stdout.split()
    assert out == ["1", "1", "1"]  # one compile each, and the second a load


def _host_events(xplane: str) -> list:
    """Every ``repro.*`` event of the host plane, per thread line, with
    the name of the innermost ``repro.*`` event enclosing it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(
                (ev.start_ns, -ev.end_ns, ev.name[len(tracing.PREFIX) :])
                for ev in line.events
                if ev.name.startswith(tracing.PREFIX)
            )
            stack = []
            for s, neg_e, name in evs:
                while stack and stack[-1][1] <= s:
                    stack.pop()
                parent = stack[-1][2] if stack else None
                out.append((name, parent, s, -neg_e))
                stack.append((s, -neg_e, name))
    return out


@pytest.fixture(scope="module")
def traced_point(tmp_path_factory):
    """One small Kripke point through ``run_experiment`` (serial, no
    profile cache, the jax reduction with Pallas in interpret mode) under
    a CPU profiler trace: its in-memory tree and the trace's events."""
    from repro.benchpark.runner import run_experiment
    from repro.benchpark.spec import ExperimentSpec, ScalePoint
    from repro.core.backend import JaxBackend, use_backend

    spec = ExperimentSpec(
        name="kripke-tracing-test",
        app="kripke",
        scaling="weak",
        points=(ScalePoint((2, 2, 1)),),
        app_params=dict(nx=4, ny=4, nz=4, n_octants=2),
    )
    out = tmp_path_factory.mktemp("trace")
    with use_backend(JaxBackend(interpret=True)):
        with jax.profiler.trace(str(out)):
            (prof,) = run_experiment(
                spec, verbose=False, cache=None, executor="serial", retries=0
            )
    (tree,) = spans("point")
    (xplane,) = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    return prof, tree, _host_events(xplane)


def test_the_point_spans_share_the_profiler_host_plane(traced_point):
    _, tree, events = traced_point
    (pt,) = [e for e in events if e[0] == "point"]
    inside = [e for e in events if pt[2] <= e[2] and e[3] <= pt[3]]
    found: dict = {}
    for name, parent, _, _ in inside:
        found.setdefault(name, {})
        if name != "point":
            found[name][parent] = found[name].get(parent, 0) + 1
    assert set(found) == set(tree.names)
    for name, layer in tree.names.items():
        assert sum(found[name].values()) == layer.count - (name == "point")
        assert found[name] == layer.parents, name
    span_ns, event_ns = tree.end_ns - tree.start_ns, pt[3] - pt[2]
    assert 0.95 * event_ns <= span_ns <= event_ns * 1.0001 + 1000


def test_the_point_tree_splits_trace_and_reduction(traced_point):
    prof, tree, _ = traced_point
    n = tree.names
    assert n["point"].parents == {}
    assert n["eval_shape"].parents == {"point": 1}
    assert n["reduce"].parents == {"point": 1}
    children = {name for name, layer in n.items() if "point" in layer.parents}
    assert children == {"eval_shape", "reduce"}  # all runner_self_ms leaves out
    assert n["matmul"].parents == {"reduce": 7}
    assert set(n["pair_counts"].parents) == {"reduce"}
    assert set(n["device_roundtrip"].parents) <= {"matmul", "pair_counts"}
    assert n["device_roundtrip"].count >= 7 + n["pair_counts"].count
    c = tree.counters
    assert {"rows", "intern_ns"} <= set(c)
    assert c["rows"] > 0 and c["intern_ns"] > 0
    assert n["eval_shape"].self_ns == n["eval_shape"].total_ns > 0
    assert n["materialize"].parents == {"reduce": 1}
    assert not prof.meta.get("degraded")
    assert sum(layer.count for layer in n.values()) < 200


def test_the_kripke_exec_program_names_its_three_scans():
    from repro.apps import kripke
    from repro.apps.stencil import Decomp3D
    from repro.core import compat

    cfg = kripke.KripkeConfig(
        decomp=Decomp3D(1, 1, 1),
        nx=4,
        ny=8,
        nz=8,
        n_dirsets=2,
        n_groupsets=2,
        n_octants=2,
        fuse_messages=False,
    )
    mesh = compat.make_mesh((1, 1, 1), ("x", "y", "z"), devices=jax.devices()[:1])
    q = jax.ShapeDtypeStruct((2, 2, 4, 8, 8, 4, 4), jnp.float32)
    text = jax.jit(kripke.distributed_sweep(cfg, mesh)).lower(q).compile().as_text()
    scopes = set(re.findall(r"kripke\.scan_[xyz]", text))
    assert scopes == {"kripke.scan_x", "kripke.scan_y", "kripke.scan_z"}
