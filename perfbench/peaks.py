"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, not a default.
"""

from __future__ import annotations

from typing import Dict

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM at 819 GB/s, per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def for_device(kind: str) -> Dict[str, float]:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add it to perfbench/peaks.py")
    return PEAKS[kind]
