"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind missing from the table is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,      # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def for_kind(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.py with its source") from None
