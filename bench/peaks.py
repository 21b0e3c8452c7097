"""Published peak rates of each accelerator, keyed by JAX's ``device_kind``.

Every roofline share and utilization the benchmark reports divides by a
number from this table.  A device that is not listed is an error: a
default would silently measure against the wrong chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak rates of ``device_kind``; KeyError for a device the table
    does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
