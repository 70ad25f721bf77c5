"""Published peaks of the accelerators the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture table):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
inter-chip interconnect per chip. A device kind missing here is an error,
never a default: ``peaks_for`` raises.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    # jax reports a v5e chip as "TPU v5 lite"
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_Bps": 819e9,
        "ici_Bps": 1600e9 / 8,
    },
}

SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The peak table of ``device_kind``; KeyError for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
