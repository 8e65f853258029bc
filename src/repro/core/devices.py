"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The one table of hardware peaks in the repo.  The trace-only models (the
runner's modeled ``meta["seconds"]``, the network layer's default link
bandwidth, the LM dry-run roofline) read it through
:data:`MODELED_DEVICE_KIND`; measurement code looks up the kind JAX
reports for the device it ran on.  A kind that is not in the table is an
error, never a default.  This module is also the one place that turns on
JAX's persistent compilation cache (:func:`use_compile_cache`).

Source of every number: Google Cloud documentation, "TPU v5e" — per chip
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of
chip-to-chip interconnect over four ICI links (50 GB/s per link).
"""

from __future__ import annotations

import os
from dataclasses import dataclass

PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e"'


@dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""

    flops_bf16: float  # FLOP/s
    hbm_bytes: float  # bytes of device memory
    hbm_bytes_per_s: float
    ici_link_bytes_per_s: float  # one inter-chip link, one direction


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bytes=16e9,
        hbm_bytes_per_s=819e9,
        ici_link_bytes_per_s=1600e9 / 8 / 4,
    ),
}

#: The chip the trace-only models assume: TPU v5e, as JAX names it.
MODELED_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}; source: {PEAKS_SOURCE})"
        ) from None


#: Where :func:`use_compile_cache` keeps compiled programs when
#: ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed directory inside the
#: checkout (listed in ``.gitignore``).  The path is part of the cache's
#: key, so it never depends on a temp name, a pid or the time.
DEFAULT_COMPILE_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` names the directory, and JAX reads
    it on its own — nothing else is set in code.  Otherwise the cache
    lives at :data:`DEFAULT_COMPILE_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
