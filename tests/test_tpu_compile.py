"""Compiles for a described TPU v5e: the main path's kernels and app steps.

Nothing here runs on a chip.  Each test compiles one program at its real
size for a v5e that is described, not attached (``jax.experimental.
topologies``), so what the chip's compiler would refuse — an unaligned
block, too much VMEM, a 64-bit type inside a kernel, a program over the
chip's memory — fails here at no chip time.  The topology is described
inside a module-scoped fixture, never at import, so test workers collect
the same tests and only the worker given this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import backend as B
from repro.core import compat
from repro.core.devices import chip_peaks

#: Rank columns the kernel is compiled at (the 8192-rank sweep point).
RANKS = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hbm(topo) -> float:
    return chip_peaks(topo.devices[0].device_kind).hbm_bytes


@pytest.mark.parametrize("op,k", [("sum", 2), ("max", 1), ("min", 1)])
def test_pallas_segment_reduce_compiles_for_v5e(topo, one_chip, op, k):
    """The segmented reduce at 8192 rank columns is a Mosaic kernel, with
    VMEM use fixed by its tiles and nothing beyond its operands in HBM."""
    n_rows, n_sb = 16 * B._SEG_ROWS, 2
    n_rb = n_rows // B._SEG_ROWS
    fn = B._seg_kernel(op, k, n_rows, n_sb, RANKS, False)
    i32 = jnp.int32
    table = [_sds((n_rb,), i32, one_chip) for _ in range(4)]
    vals = (k, n_rows, RANKS) if op == "sum" else (n_rows, RANKS)
    compiled = fn.lower(
        *table, _sds((n_rows,), i32, one_chip), _sds(vals, i32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    out_rows = n_sb * B._SEG_BLOCK
    assert mem.argument_size_in_bytes >= 4 * int(np.prod(vals))
    assert mem.output_size_in_bytes == 4 * k * out_rows * RANKS
    assert mem.temp_size_in_bytes <= 4 * k * out_rows * RANKS


def test_limb_dot_compiles_for_v5e(topo, one_chip):
    """The exact matmul's int8 limb dot lowers to an s32 MXU convolution
    and fits the chip at a 64-region x 4096-struct x 8192-rank reduction
    with five limbs of weights."""
    g, s, ka = 64, 4096, 5
    compiled = (
        B._limb_dot_fn()
        .lower(
            _sds((ka * g, s), jnp.int8, one_chip),
            _sds((s, RANKS), jnp.int8, one_chip),
        )
        .compile()
    )
    text = compiled.as_text()
    assert "s32[320,8192]" in text and "convolution" in text
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 4 * ka * g * RANKS
    assert mem.temp_size_in_bytes < _hbm(topo) / 4


@pytest.mark.parametrize("kernel", ["sum", "max", "min", "limb_dot"])
def test_device_code_keeps_its_stable_name_for_v5e(topo, one_chip, kernel):
    """The Pallas kernels and the limb dot keep the names a device trace
    groups their time by: ``repro_segment_<op>`` on the kernel's custom
    call, ``repro.limb_dot`` in the dot's op metadata."""
    i32 = jnp.int32
    if kernel == "limb_dot":
        fn = B._limb_dot_fn()
        args = [
            _sds((8, 256), jnp.int8, one_chip),
            _sds((256, 128), jnp.int8, one_chip),
        ]
        want = "repro.limb_dot"
    else:
        n_rows = 2 * B._SEG_ROWS
        fn = B._seg_kernel(kernel, 1, n_rows, 1, 128, False)
        vals = (1, n_rows, 128) if kernel == "sum" else (n_rows, 128)
        args = [_sds((2,), i32, one_chip) for _ in range(4)]
        args += [_sds((n_rows,), i32, one_chip), _sds(vals, i32, one_chip)]
        want = f"repro_segment_{kernel}"
    text = fn.lower(*args).compile().as_text()
    assert want in text


def _app_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )


@pytest.fixture(scope="module")
def tioga_sweep(topo):
    """The Tioga (2,2,2) global problem — 32x64x64 zones, 6x6 sets, 4x4
    dirs/groups, 2 unfused octants — compiled once per mesh."""
    from repro.apps import kripke
    from repro.apps.stencil import Decomp3D

    compiled = {}

    def get(decomp):
        if decomp not in compiled:
            n = int(np.prod(decomp))
            mesh = compat.make_mesh(decomp, ("x", "y", "z"), devices=topo.devices[:n])
            zones = (32, 64, 64)
            nx, ny, nz = (z // d for z, d in zip(zones, decomp))
            cfg = kripke.KripkeConfig(
                decomp=Decomp3D(*decomp),
                nx=nx,
                ny=ny,
                nz=nz,
                n_octants=2,
                fuse_messages=False,
            )
            spec = P(None, None, "x", "y", "z", None, None)
            q = _sds((6, 6, *zones, 4, 4), jnp.float32, NamedSharding(mesh, spec))
            sweep = jax.jit(kripke.distributed_sweep(cfg, mesh))
            compiled[decomp] = sweep.lower(q).compile()
        return compiled[decomp]

    return get


def test_kripke_tioga_sweep_compiles_on_one_chip(tioga_sweep, topo):
    """The Tioga global problem fits one chip's HBM with the regions in the
    program."""
    compiled = tioga_sweep((1, 1, 1))
    assert "commr::main" in compiled.as_text()
    q_bytes = 4 * 6 * 6 * 32 * 64 * 64 * 4 * 4
    assert compiled.memory_analysis().argument_size_in_bytes >= q_bytes
    assert _app_bytes(compiled) < _hbm(topo)


_HLO_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\](?:\{([\d,]*))?")


def _swept_plane_writes(text: str, scopes) -> list:
    """``(op_name, swept dims, minor-to-major layout)`` of each
    dynamic-update-slice under one of ``scopes``: the swept dims are those
    along which the update is smaller than the array it is written into."""
    lines = text.splitlines()
    dims = {}
    for m in filter(None, map(_HLO_DEF.match, lines)):
        dims[m[1]] = [int(d) for d in m[2].split(",") if d]
    found = []
    for line in lines:
        op_name = re.search(r'op_name="([^"]*)"', line)
        if " dynamic-update-slice(" not in line or not op_name:
            continue
        if not any(s in op_name[1] for s in scopes):
            continue
        args = re.search(r"dynamic-update-slice\(%([^,\s]+), %([^,\s]+)", line)
        operand, update = dims[args[1]], dims[args[2]]
        swept = [i for i, (o, u) in enumerate(zip(operand, update)) if u < o]
        layout = [int(d) for d in _HLO_DEF.match(line)[3].split(",")]
        found.append((op_name[1], swept, layout))
    return found


@pytest.mark.parametrize(
    "decomp,parent_temp", [((1, 1, 1), 2_417_725_440), ((2, 2, 1), 1_502_599_680)]
)
def test_kripke_x_and_y_sweeps_write_whole_tiles(tioga_sweep, decomp, parent_temp):
    """The x and y recurrences write each plane in place: no plane write
    under ``kripke.scan_x``/``_y`` has its swept dim among the two minor-most
    of its layout, where one plane would be one row of every (8,128) tile.
    Temp memory is no more than the stacked-scan form needed (the bytes
    given), and the three scan scopes are still in the optimized HLO."""
    compiled = tioga_sweep(decomp)
    text = compiled.as_text()
    writes = _swept_plane_writes(text, ("kripke.scan_x", "kripke.scan_y"))
    assert any("kripke.scan_x" in w[0] for w in writes)
    assert any("kripke.scan_y" in w[0] for w in writes)
    for op_name, swept, layout in writes:
        assert not set(swept) & set(layout[:2]), (op_name, swept, layout)
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp
    for axis in "xyz":
        assert f"kripke.scan_{axis}" in text


@pytest.mark.parametrize("decomp", [(1, 1, 1), (2, 2, 1)])
def test_laghos_step_compiles(topo, decomp):
    """Laghos 512x512 strong, 2 steps, on one chip and across four."""
    from repro.apps import laghos
    from repro.apps.stencil import Decomp3D

    n = int(np.prod(decomp))
    mesh = compat.make_mesh(decomp, ("x", "y", "z"), devices=topo.devices[:n])
    cfg = laghos.LaghosConfig(decomp=Decomp3D(*decomp), nx=512, ny=512, n_steps=2)
    s = _sds((512, 512), jnp.float32, NamedSharding(mesh, P()))
    state = dict(rho=s, e=s, vx=s, vy=s)
    compiled = jax.jit(laghos.run_steps(cfg, mesh)).lower(state).compile()
    text = compiled.as_text()
    assert "commr::halo_exchange" in text
    if n > 1:
        assert "collective-permute" in text
    assert _app_bytes(compiled) < _hbm(topo) / 100


#: Temp bytes of the amg-table3 exec step for a v5e (compile): the whole
#: 22-iteration solve at 256x256x128 float32 on one chip.  A restriction
#: that splits each axis into a size-2 minor dim needs 3,044,500,992.
AMG_DANE_TEMP = 960_377_344


def test_amg_restriction_keeps_the_residual_layout(one_chip):
    """The AMG restriction at the Dane fine level (256x256x128 float32)
    needs no temp buffer and makes no value whose minor dim is 2: such a
    dim sits in the 128 lanes of a (8,128) tile, 64x the residual's
    bytes."""
    from repro.apps import amg

    r = _sds((256, 256, 128), jnp.float32, one_chip)
    compiled = jax.jit(amg._restrict).lower(r).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    assert not re.findall(r"\[[\d,]*,2\]", compiled.as_text())


def test_amg_dane_solve_compiles_on_one_chip(topo):
    """The amg-table3 exec step — the Dane (8,8,8) problem as one
    256x256x128 float32 grid, the configuration's whole PCG–AMG solve —
    compiles on mesh (1,1,1) with its ``commr::`` region scopes and the
    ``amg.*`` device scopes, in the temp bytes recorded above."""
    import json

    from repro.apps import amg
    from repro.apps.stencil import Decomp3D

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs", "amg-table3.json")) as f:
        cfg = json.load(f)
    shape = [z * p for z, p in zip(cfg["zones_per_rank"], cfg["exec_point"])]
    mesh = compat.make_mesh((1, 1, 1), ("x", "y", "z"), devices=topo.devices[:1])
    acfg = amg.AMGConfig(
        decomp=Decomp3D(1, 1, 1),
        nx=shape[0],
        ny=shape[1],
        nz=shape[2],
        n_pre=cfg["n_pre"],
        n_post=cfg["n_post"],
        n_coarse_iters=cfg["n_coarse_iters"],
        omega=cfg["omega"],
        min_global=cfg["min_global"],
        n_cycles=cfg["n_cycles"],
    )
    f = _sds(tuple(shape), jnp.float32, NamedSharding(mesh, P("x", "y", "z")))
    compiled = jax.jit(amg.solve(acfg, mesh)).lower(f).compile()
    text = compiled.as_text()
    levels = acfg.n_dist_levels()
    assert levels == 4
    regions = {"main", "reduce_norm", "MatVecComm"}
    regions |= {f"mg_level_{k}" for k in range(levels)}
    assert regions <= set(re.findall(r"commr::(\w+)", text))
    phases = {"smooth", "residual", "restrict", "prolong", "coarse", "matvec", "krylov"}
    assert phases <= set(re.findall(r"amg\.(\w+)/", text))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp <= AMG_DANE_TEMP
    assert _app_bytes(compiled) < _hbm(topo) / 4
