"""Published peak rates of one chip, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default: a share of
a peak that was guessed would be a number under a false name.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in bf16,
# 393 TOP/s in int8, 16 GB of HBM at 819 GB/s. JAX names the chip
# "TPU v5 lite".
_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict:
    """The peak table row of ``device_kind``; ValueError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
