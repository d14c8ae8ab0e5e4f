"""Published per-chip peaks — the one table every MFU, roofline and
bandwidth-utilization denominator in the repo reads.

Keyed by ``jax.devices()[0].device_kind``. A device that is not in the
table is an error, not a default: a utilization against another chip's
peak is a wrong number under a right name.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

__all__ = ["ChipPeak", "PEAKS", "chip_peak"]


@dataclasses.dataclass(frozen=True)
class ChipPeak:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    source: str


PEAKS = {
    # 394e12 is the int8 rate, not the bf16 one
    "TPU v5 lite": ChipPeak(
        197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}


def chip_peak(device_kind: Optional[str] = None) -> ChipPeak:
    """Peaks of ``device_kind`` (default: the attached default device).
    Analysing a capture away from the chip that made it? Pass the kind
    that made it."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add it to apex_tpu/prof/peaks.py with its "
            f"source, or pass explicit peaks") from None
