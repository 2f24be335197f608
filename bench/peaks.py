"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak is only as good as the peak it divides by.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2 at
    # 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in ``PEAKS``."""


def peaks_of(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``UnknownDevice`` if absent."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to bench/peaks.py with their source") from None
