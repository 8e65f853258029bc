"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

The benchmark's own copy: a program change cannot move the yardstick.  A
kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass

SOURCE = (
    'Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16, '
    "393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of ICI"
)


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float  # FLOP/s
    ops_int8: float  # OP/s
    hbm_bytes: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # all links of one chip, one direction


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12,
        ops_int8=393e12,
        hbm_bytes=16e9,
        hbm_bytes_per_s=819e9,
        ici_bytes_per_s=1600e9 / 8,
    ),
}


def peaks(device_kind: str) -> Peaks:
    """Peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)}; source: {SOURCE})"
        ) from None
