"""The one module that touches version-sensitive JAX APIs.

Everything in this repo that builds a device mesh, an abstract (trace-only)
mesh, a shard-mapped function, or scopes 64-bit mode goes through this
module and **only** this module.  The motivation is the same one ucTrace
gives for layering a profiler behind a stable abstraction: the underlying
stack churns, and a trace-time profiling substrate must not die with it.
When JAX renames or moves one of these APIs, the fix lands here and no
caller changes.

Supported JAX version
---------------------
jax 0.9.0 (with the matching libtpu on TPU hosts) — the one installation
the repo is built, tested and run on.  CI pins the same version.

Contract
--------
``make_mesh(axis_shapes, axis_names, devices=None)``
    Real device mesh with every axis in Auto mode.
``abstract_mesh(axis_shapes, axis_names)``
    Trace-only mesh (no devices needed) usable with ``shard_map`` +
    ``jax.eval_shape`` — the substrate under all paper-scale profiling.
``shard_map(fn, mesh=..., in_specs=..., out_specs=..., check_vma=None)``
    The repo-wide spelling of shard_map; ``None`` means library default.
``axis_size(axis_name)``
    Size of a named mesh axis (tuple names multiply), inside shard_map.
``enable_x64()``
    Context manager scoping 64-bit dtypes to a block (the exact int64
    reduction backend enables it per call, never process-wide).
``axis_type_kwargs(n_axes)``
    ``{"axis_types": (AxisType.Auto,) * n_axes}`` for callers that must
    invoke ``jax.make_mesh`` directly.
``cost_analysis(compiled)``
    ``compiled.cost_analysis()`` as a dict.
``AxisType``, ``Mesh``, ``NamedSharding``, ``PartitionSpec``
    Re-exports.

Callers must not import ``AxisType``, ``AbstractMesh``, ``shard_map`` or
the x64 scope from jax directly; new version drift then lands in exactly
one file.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import (  # noqa: F401  (re-exports: one-stop import)
    AbstractMesh as _AbstractMesh,
    AxisType,
    Mesh,
    NamedSharding,
    PartitionSpec,
)

def axis_type_kwargs(n_axes: int) -> dict:
    """Kwargs marking ``n_axes`` mesh axes Auto."""
    return {"axis_types": (AxisType.Auto,) * n_axes}


def enable_x64():
    """Context manager enabling 64-bit dtypes inside its block only."""
    return jax.enable_x64(True)


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma: Optional[bool] = None):
    """``jax.shard_map`` (the only spelling used in this repo).

    ``check_vma=None`` leaves replication/VMA checking at the library
    default; an explicit bool is forwarded.
    """
    kwargs: dict = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def axis_size(axis_name):
    """Size of a named mesh axis inside shard_map; a tuple of names gives
    the product of their sizes."""
    if isinstance(axis_name, (tuple, list)):
        out = 1
        for a in axis_name:
            out *= jax.lax.axis_size(a)
        return out
    return jax.lax.axis_size(axis_name)


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Optional[Sequence[Any]] = None,
):
    """Real device mesh with Auto axis types."""
    shapes, names = tuple(axis_shapes), tuple(axis_names)
    kwargs: dict = axis_type_kwargs(len(shapes))
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(shapes, names, **kwargs)


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """Trace-only mesh: shard_map structure without any devices.

    This is what lets paper-scale rank counts (64..131072) profile on a
    single-CPU host — ``jax.eval_shape`` over a shard-mapped function on
    an abstract mesh records the full communication structure.
    """
    shapes, names = tuple(axis_shapes), tuple(axis_names)
    return _AbstractMesh(shapes, names, **axis_type_kwargs(len(shapes)))


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when unavailable)."""
    return dict(compiled.cost_analysis() or {})


def describe() -> dict:
    """Which jax this substrate runs on (for debugging)."""
    return {"jax_version": jax.__version__}
