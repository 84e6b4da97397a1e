"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` jax reports. A chip that is not here is an error, never a
default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> Dict[str, object]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            "with its source to benchmark/harness/peaks.py")
    return PEAKS[device_kind]
