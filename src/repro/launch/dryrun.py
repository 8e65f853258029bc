import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the device
# count at first init).  Everything below is ordinary.

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import registry            # noqa: E402
from repro.core import compat                 # noqa: E402
from repro.configs.base import SHAPES, model_flops  # noqa: E402
from repro.core.hlo import scan_hlo_collectives  # noqa: E402
from repro.core.devices import MODELED_DEVICE_KIND, chip_peaks  # noqa: E402
from repro.core.hlo_cost import analyze_cost  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_shape_dict  # noqa: E402
from repro.parallel.context import parallel_context  # noqa: E402
from repro.parallel.sharding import default_plan     # noqa: E402
from repro.train import steps as S                   # noqa: E402

# TPU v5e hardware model (repro.core.devices)
_PEAKS = chip_peaks(MODELED_DEVICE_KIND)
PEAK_FLOPS = _PEAKS.flops_bf16           # bf16 / chip
HBM_BW = _PEAKS.hbm_bytes_per_s          # bytes/s / chip
LINK_BW = _PEAKS.ici_link_bytes_per_s    # bytes/s / ICI link

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "benchmarks", "results",
                           "dryrun")

# long_500k runs only for sub-quadratic archs (DESIGN.md §4).
LONG_OK = ("zamba2-1.2b", "xlstm-1.3b")


def cell_is_applicable(arch: str, shape_name: str) -> tuple:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return False, ("pure full-attention stack: 512k dense decode "
                       "excluded per assignment; see DESIGN.md §4")
    return True, ""


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               plan_overrides: dict | None = None,
               cfg_overrides: dict | None = None):
    """Build + lower + compile one (arch x shape x mesh) cell.

    Returns (record, compiled); record carries memory/cost/collective
    numbers for §Dry-run and §Roofline.  ``cfg_overrides`` replaces
    ModelConfig fields (hillclimb lever, e.g. mlstm chunk size).
    """
    from dataclasses import replace as _replace
    cfg = registry.get(arch)
    if cfg_overrides:
        cfg = _replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    mesh_shape = mesh_shape_dict(mesh)
    plan = default_plan(cfg, mesh_shape)
    if shape.kind == "decode":
        # single-token step: nothing to gain from seq sharding of the
        # 1-wide activations; cache sharding is governed by kv_seq.
        plan = plan.override(seq=None)
    dp = mesh_shape.get("pod", 1) * mesh_shape.get("data", 1)
    if shape.global_batch % dp != 0:
        # e.g. long_500k's global_batch=1: replicate the batch dim; the
        # cache/state sharding (kv_seq / model axes) carries the scale-out.
        plan = plan.override(batch=None)
    if plan_overrides:
        plan = plan.override(**plan_overrides)

    t0 = time.time()
    with parallel_context(mesh, plan):
        if shape.kind == "train":
            step, model = S.make_train_step(cfg)
            aparams = model.abstract(mesh, plan)
            aopt = S.abstract_opt_state(cfg, mesh, plan)
            abatch = S.batch_specs(cfg, shape, mesh, plan)
            lowered = jax.jit(step).lower(aparams, aopt, abatch)
        elif shape.kind == "prefill":
            step, model = S.make_prefill_step(cfg, s_max=shape.seq_len)
            aparams = model.abstract(mesh, plan)
            abatch = S.batch_specs(cfg, shape, mesh, plan)
            abatch.pop("labels", None)
            lowered = jax.jit(step).lower(aparams, abatch)
        else:  # decode
            step, model = S.make_decode_step(cfg)
            aparams = model.abstract(mesh, plan)
            acaches = S.cache_specs(cfg, shape, mesh, plan)
            atok = S.decode_token_specs(cfg, shape, mesh, plan)
            lowered = jax.jit(step, static_argnames=()).lower(
                aparams, acaches, atok, jnp.int32(shape.seq_len - 1))
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    xla_cost = compat.cost_analysis(compiled)
    hlo = compiled.as_text()
    # Columnar HLO scan: one buffer per compiled module, summarized with
    # one vectorized pass (no per-op CollectiveOp objects).
    hlo_buf = scan_hlo_collectives(hlo, total_devices=n_dev, with_loops=True)
    summ = hlo_buf.summarize()
    # Trip-count-correct per-device cost (XLA's cost_analysis counts scan
    # bodies once — see repro.core.hlo_cost).
    cost = analyze_cost(hlo)

    flops_dev = float(cost.flops)
    bytes_dev = float(cost.bytes_accessed)
    wire_dev = float(summ.total_wire_bytes)

    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    coll_s = wire_dev / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * n_dev

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "plan": plan.describe(),
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "total_bytes": (mem.argument_size_in_bytes
                            + mem.temp_size_in_bytes
                            + mem.output_size_in_bytes),
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "xla_flops_unscaled": float(xla_cost.get("flops", 0.0)),
                 "xla_bytes_unscaled": float(
                     xla_cost.get("bytes accessed", 0.0))},
        "collectives": {
            "wire_bytes_per_device": wire_dev,
            "operand_bytes_per_device": float(summ.total_operand_bytes),
            "n_ops": summ.n_ops,
            "by_kind": {k: list(v) for k, v in summ.by_kind.items()},
            "by_region": {k: list(v) for k, v in summ.by_region.items()},
        },
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "step_s_lower_bound": max(terms.values()),
            "model_flops": mf,
            "hlo_flops_global": hlo_flops_global,
            "model_to_hlo_flops": (mf / hlo_flops_global
                                   if hlo_flops_global else 0.0),
            # useful-FLOPs throughput at the roofline-limited step time,
            # as a fraction of aggregate peak (the §Perf score):
            "roofline_fraction": (
                mf / max(terms.values()) / (PEAK_FLOPS * n_dev)
                if max(terms.values()) > 0 else 0.0),
        },
    }
    return record, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str, plan_overrides=None, tag: str = "") -> dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}{('__' + tag) if tag else ''}"
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    applicable, why = cell_is_applicable(arch, shape_name)
    if not applicable:
        record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
                  "status": "skipped", "reason": why}
    else:
        try:
            record, _ = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                   plan_overrides=plan_overrides)
        except Exception as e:  # a failing cell is a bug to fix, but keep
            record = {"arch": arch, "shape": shape_name,  # sweeping
                      "mesh": mesh_tag, "status": "error",
                      "error": f"{type(e).__name__}: {e}",
                      "trace": traceback.format_exc()[-2000:]}
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["16x16", "2x16x16", "both"],
                    default="both")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args()

    archs = registry.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"16x16": [False], "2x16x16": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape_name, mp, args.out)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dominant={r['dominant']}"
                             f" step>={r['step_s_lower_bound']:.4f}s"
                             f" mem={rec['memory']['total_bytes']/2**30:.2f}GiB")
                elif status == "error":
                    extra = " " + rec.get("error", "")[:120]
                print(f"[{time.strftime('%H:%M:%S')}] {arch} {shape_name} "
                      f"{'2x16x16' if mp else '16x16'}: {status}{extra} "
                      f"({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
