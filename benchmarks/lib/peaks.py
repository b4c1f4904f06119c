"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a roofline or MFU against a guessed peak is a wrong number."""
from __future__ import annotations

from typing import Dict

# bf16 FLOP/s and HBM bytes/s of ONE chip.
PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add them "
            f"to benchmarks/lib/peaks.py with their source"
        ) from None
