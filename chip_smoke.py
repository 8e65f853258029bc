"""Drive both main paths of the profiler once on a TPU, and check them.

Phase (a), executed apps.  kripke, amg, laghos and beatnik run through
their jit-able entry points with their communication regions on, at the
global problem of the paper's Tioga (2, 2, 2) point (per-rank sizes from
``repro.benchpark.spec``), on a (1, 1, 1) mesh on one chip.  Each result
is compared with the same function run on the host's CPU device in this
process, at the tolerances of the 8-rank parity tests.

Phase (b), trace and reduce.  The 8192-rank ``kripke-weak-scale`` point is
traced through ``run_experiment`` and reduced once on the jax backend
(Pallas compiled for the chip) and once on NumPy; the profiles' ``to_json()``
and the modeled network rows must be byte-identical.  The Pallas segmented
reduce also runs on that trace's (row x link) grid against NumPy.

``--four-chips`` runs only the cross-chip path: the four apps on a
(2, 2, 1) mesh over four chips at the published per-rank sizes, checked
against their single-domain reference oracles, with every output sharded
over four devices.

Every measured number is printed on its own line, tagged with the
platform, device kind and device count.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed;
when JAX finds no TPU the script exits non-zero without it.

Run: ``python chip_smoke.py`` (one chip) or ``python chip_smoke.py
--four-chips`` (four chips).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from dataclasses import replace

HERE = os.path.dirname(os.path.abspath(__file__))

#: Timed calls per executed app, after one warm-up call.
STEPS = 3


def _emit(device: dict, **fields) -> None:
    print(json.dumps({**fields, **device}, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# Phase (a) / --four-chips: executed apps
# ---------------------------------------------------------------------------


def _close(tol: dict):
    import numpy as np

    def check(got, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)

    return check


def app_cases(decomp: tuple, small: bool = False) -> list:
    """One case per app: ``(name, cfg, make_input, run(cfg, mesh), reference,
    checks)``.  ``decomp`` is the mesh; sizes are the Tioga (2, 2, 2)
    point's global problem on (1, 1, 1), the published per-rank sizes on
    larger meshes, and tiny when ``small`` (CPU rehearsal)."""
    from repro.apps import amg, beatnik, kripke, laghos
    from repro.apps.stencil import Decomp3D

    dc = Decomp3D(*decomp)
    single = decomp == (1, 1, 1)
    k = 4 if small else 1  # CPU rehearsal: a quarter of each extent
    kcfg = kripke.KripkeConfig(
        decomp=dc,
        nx=(32 if single else 16) // k,
        ny=(64 if single else 32) // k,
        nz=(64 if single else 32) // k,
        n_octants=2,
        fuse_messages=False,
        **(dict(n_dirsets=2, n_groupsets=2) if small else {}),
    )
    acfg = amg.AMGConfig(
        decomp=dc,
        nx=(64 if single else 32) // k,
        ny=(64 if single else 32) // k,
        nz=(32 if single else 16) // k,
    )
    lcfg = laghos.LaghosConfig(decomp=dc, nx=512 // k, ny=512 // k, n_steps=2)
    kb = 2 if small else 1
    bcfg = beatnik.BeatnikConfig(
        decomp=dc,
        nx=(64 if single else 32) // kb,
        ny=(64 if single else 32) // kb,
        n_steps=4,
    )
    field = _close(dict(rtol=5e-5, atol=5e-6))
    return [
        (
            "laghos",
            lcfg,
            lambda: laghos.make_state(lcfg),
            laghos.run_steps,
            lambda: laghos.reference_steps(lcfg),
            lambda out, ref: (
                [field(out[0][f], ref[0][f]) for f in out[0]],
                _close(dict(rtol=1e-5))(out[1], ref[1]),
            ),
        ),
        (
            "beatnik",
            bcfg,
            lambda: beatnik.make_state(bcfg),
            beatnik.run_steps,
            lambda: beatnik.reference_steps(bcfg),
            lambda out, ref: (
                field(out[0][0], ref[0][0]),
                field(out[0][1], ref[0][1]),
                _close(dict(rtol=1e-4))(out[1], ref[1]),
            ),
        ),
        (
            "amg",
            acfg,
            lambda: amg.make_rhs(acfg),
            amg.solve,
            lambda: amg.reference_solve(acfg)[0],
            lambda out, ref: (
                _close(dict(rtol=2e-4, atol=2e-5))(out[0], ref[0]),
                _close(dict(rtol=1e-4))(out[1], ref[1]),
            ),
        ),
        (
            "kripke",
            kcfg,
            lambda: kripke.make_source(kcfg, global_shape=True),
            kripke.distributed_sweep,
            lambda: kripke.reference_sweep(kcfg),
            _close(dict(rtol=2e-5, atol=2e-5)),
        ),
    ]


def _host_tree(make_input):
    """The app's input, built on the host CPU so that building it leaves
    no mark on the chip's peak memory."""
    import jax
    import numpy as np

    with jax.default_device(jax.devices("cpu")[0]):
        return jax.tree.map(np.asarray, make_input())


def _timed(compiled, x) -> tuple:
    """One warm-up call, then :data:`STEPS` calls timed to
    ``block_until_ready``; returns ``(output, seconds per step)``."""
    import jax

    out = jax.block_until_ready(compiled(x))
    steps = []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(x))
        steps.append(time.perf_counter() - t0)
    return out, steps


def run_app(case, mesh, host_mesh, device: dict) -> None:
    """Run one app on ``mesh`` with regions on, time it, and check it
    against the same function on ``host_mesh`` (the host CPU)."""
    import jax

    name, cfg, make_input, run, _, check = case
    x = _host_tree(make_input)
    on_dev = jax.device_put(x, mesh.devices.flat[0])
    t0 = time.perf_counter()
    compiled = jax.jit(run(cfg, mesh)).lower(on_dev).compile()
    compile_s = time.perf_counter() - t0
    if "commr::main" not in compiled.as_text():
        raise AssertionError(f"{name}: comm regions missing from the program")
    out, steps = _timed(compiled, on_dev)
    stats = mesh.devices.flat[0].memory_stats() or {}
    on_host = jax.device_put(x, host_mesh.devices.flat[0])
    host_out = jax.jit(run(cfg, host_mesh))(on_host)
    check(out, host_out)
    _emit(
        device,
        phase="apps",
        app=name,
        mesh=list(mesh.devices.shape),
        compile_seconds=compile_s,
        step_seconds=steps,
        step_seconds_median=statistics.median(steps),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        matches_cpu=True,
    )


def run_app_sharded(case, mesh, device: dict) -> None:
    """Run one app across every device of ``mesh``; check it against the
    app's single-domain reference oracle and that each output is sharded
    over all of the mesh's devices."""
    import jax

    from jax.sharding import NamedSharding, PartitionSpec

    name, cfg, make_input, run, reference, check = case
    x = _host_tree(make_input)
    # on every chip before the clock starts: steps time no host transfer
    on_mesh = jax.device_put(x, NamedSharding(mesh, PartitionSpec()))
    fn = jax.jit(run(cfg, mesh))
    out, steps = _timed(fn, on_mesh)
    n = mesh.devices.size
    spans = [len(leaf.sharding.device_set) for leaf in jax.tree.leaves(out)]
    if any(s != n for s in spans):
        raise AssertionError(f"{name}: outputs span {spans} devices, not {n}")
    ref = jax.jit(reference())(x)
    check(out, ref)
    _emit(
        device,
        phase="four_chips",
        app=name,
        mesh=list(mesh.devices.shape),
        step_seconds=steps,
        step_seconds_median=statistics.median(steps),
        output_devices=spans,
        matches_reference=True,
    )


# ---------------------------------------------------------------------------
# Phase (b): trace one sweep point and reduce it on the device
# ---------------------------------------------------------------------------


def reduce_point(spec, n_ranks: int, jax_backend, device: dict) -> None:
    """Trace ``spec``'s ``n_ranks`` point through ``run_experiment`` on the
    jax and NumPy backends and require byte-identical results."""
    import numpy as np

    from repro.benchpark.runner import run_experiment
    from repro.core.backend import (
        NumpyBackend,
        resolve_backend,
        segment_spans,
        use_backend,
    )
    from repro.core.network import NetworkModeledProfiler, struct_costs
    from repro.core.profiler import CommPatternProfiler, trace_observer

    pt = next(p for p in spec.points if p.n_ranks == n_ranks)
    one = replace(spec, points=(pt,))
    held = {}

    def observe(rec, *, name, replication, meta):
        be = resolve_backend()  # the backend run_experiment installed
        t0 = time.perf_counter()
        prof = CommPatternProfiler.from_recorder(
            rec, name=name, replication=replication, meta=meta
        )
        held[be.name] = (rec, time.perf_counter() - t0)
        return prof

    profs = {}
    for be in (jax_backend, NumpyBackend()):
        with use_backend(be), trace_observer(observe):
            (prof,) = run_experiment(
                one, verbose=False, executor="serial", cache=None, retries=0
            )
        if prof.meta.get("degraded"):
            raise AssertionError(f"{be.name}: point degraded: {prof.meta}")
        profs[be.name] = prof
    if profs["jax"].to_json() != profs["numpy"].to_json():
        raise AssertionError("jax and numpy profiles differ")
    rec = held["jax"][0]

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    rows, warm, net = {}, {}, {}
    for be in (jax_backend, NumpyBackend()):
        rows[be.name] = NetworkModeledProfiler.region_rows(
            rec, n_ranks=n_ranks, backend=be
        )
        _, warm[be.name] = timed(
            lambda: CommPatternProfiler.from_recorder(rec, backend=be)
        )
        _, net[be.name] = timed(
            lambda: NetworkModeledProfiler.region_rows(
                rec, n_ranks=n_ranks, backend=be
            )
        )
    if json.dumps(rows["jax"], sort_keys=True) != json.dumps(
        rows["numpy"], sort_keys=True
    ):
        raise AssertionError("jax and numpy network rows differ")

    # the Pallas segmented reduce on the trace's (row x link) grid, by region
    buf = rec.buffer
    order, _, starts, ends = segment_spans(buf.region_ids)
    sid = buf.struct_ids if order is None else buf.struct_ids[order]
    grid = struct_costs(buf.structs).link_grid[sid]
    kernel = {}
    for ufunc in (np.add, np.maximum, np.minimum):
        want = NumpyBackend().block_reduce(grid, starts, ends, ufunc)
        jax_backend.block_reduce(grid, starts, ends, ufunc)  # compile
        got, kernel[ufunc.__name__] = timed(
            lambda: jax_backend.block_reduce(grid, starts, ends, ufunc)
        )
        if not np.array_equal(got, want):
            raise AssertionError(f"block_reduce({ufunc.__name__}) differs")

    _emit(
        device,
        phase="reduce",
        experiment=spec.name,
        n_ranks=n_ranks,
        regions=len(profs["jax"].regions),
        trace_rows=int(buf.n_rows),
        unique_structs=int(buf.structs.n_structs),
        profile_json_identical=True,
        network_rows_identical=True,
        measured_on=device["device_kind"],
        reduce_seconds_first_call={k: v[1] for k, v in held.items()},
        reduce_seconds_warm=warm,
        network_rows_seconds_warm=net,
        pallas_grid=list(grid.shape),
        pallas_block_reduce_seconds=kernel,
        pallas_compiled=jax_backend.use_pallas and not jax_backend.interpret,
    )


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the (2, 2, 1) cross-chip path over four chips",
    )
    args = ap.parse_args(argv)

    # The host CPU runs the in-process reference next to the chip.
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees {devices[0].platform!r}); "
            "this script only runs on the chip",
            file=sys.stderr,
        )
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(
            f"chip_smoke: needs {want} chips, found {len(devices)}", file=sys.stderr
        )
        return 1

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.core import compat
    from repro.core.backend import JaxBackend, resolve_backend
    from repro.core.devices import use_compile_cache

    cache_dir = use_compile_cache()
    device = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    _emit(device, phase="setup", compile_cache=cache_dir, jax=jax.__version__)
    axes = ("x", "y", "z")

    if args.four_chips:
        mesh = compat.make_mesh((2, 2, 1), axes, devices=devices[:4])
        for case in app_cases((2, 2, 1)):
            run_app_sharded(case, mesh, device)
    else:
        from repro.benchpark.spec import SCALE_EXPERIMENTS

        mesh = compat.make_mesh((1, 1, 1), axes, devices=devices[:1])
        cpu = jax.devices("cpu")[:1]
        host = compat.make_mesh((1, 1, 1), axes, devices=cpu)
        for case in app_cases((1, 1, 1)):
            run_app(case, mesh, host, device)
        be = resolve_backend("jax")
        if not (isinstance(be, JaxBackend) and be.use_pallas and not be.interpret):
            raise AssertionError("the jax backend is not running compiled Pallas")
        reduce_point(SCALE_EXPERIMENTS["kripke-weak-scale"], 8192, be, device)

    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
