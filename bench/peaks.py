"""Peak rates of each accelerator, keyed by JAX's `device_kind`.

A device that is not in this table is an error, never a default: a
roofline or utilization share against a guessed peak means nothing.
"""

from __future__ import annotations

from typing import Dict

#: device_kind -> per-chip peaks
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s, dense bfloat16 matmul
        "int8_ops": 393e12,        # OP/s
        "hbm_bytes_per_s": 819e9,  # B/s
        "hbm_bytes": 16e9,         # B
    },
}

SOURCES = {
    "TPU v5 lite": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                   "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None
