"""kripke-table3, the program side: the Kripke weak-scaling experiment at the
configuration's rank counts, as a benchpark ``ExperimentSpec``."""

from __future__ import annotations


def spec(cfg: dict):
    from repro.benchpark.spec import ExperimentSpec, ScalePoint

    nx, ny, nz = cfg["zones_per_rank"]
    return ExperimentSpec(
        name=cfg["experiment"],
        app=cfg["app"],
        scaling=cfg["scaling"],
        points=tuple(ScalePoint(tuple(p)) for p in cfg["points"]),
        app_params=dict(
            nx=nx,
            ny=ny,
            nz=nz,
            n_dirsets=cfg["n_dirsets"],
            n_groupsets=cfg["n_groupsets"],
            dirs_per_set=cfg["dirs_per_set"],
            groups_per_set=cfg["groups_per_set"],
            n_octants=cfg["n_octants"],
            fuse_messages=cfg["fuse_messages"],
            dtype=cfg["dtype"],
        ),
    )
