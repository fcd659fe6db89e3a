"""Peak rates of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 819 GB/s HBM bandwidth per chip, 16 GB HBM.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "source": "cloud.google.com/tpu/docs/v5e"}

PEAKS: Dict[str, Dict] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add it to bench/peaks.py with its source") from None
