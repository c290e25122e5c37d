"""Peak rates of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 394 TOP/s int8, 16 GiB HBM2 at 819 GB/s. A device
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 394e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 394e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30},
}


def peak_for(device_kind: str) -> Dict[str, float]:
    """The peak rates of ``device_kind``; raises for an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
