"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports. A chip that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s).
"""
from __future__ import annotations

SOURCE = 'Google Cloud documentation, "TPU v5e"'

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_s": 197e12,
        "int8_op_s": 393e12,
        "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises ``UnknownDevice``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
